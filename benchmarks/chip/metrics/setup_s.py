"""Seconds from process start to the start of the window: generating
the inputs, building the graph, compiling or loading every program and
warming it (host clock)."""


def read(record):
    return record["setup_s"]
