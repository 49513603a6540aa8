"""Compile the census's device steps for a TPU v5e that is described, not
attached.

The TPU compiler ships with jaxlib, so these tests lower and compile the
steps of the main (``jnp``) path, the repaired Pallas histogram kernel
and the per-chip megastep of partitioned runs at the shapes
``chip_smoke.py`` runs — the cit-Patents-scale batch graph, 4M-item
chunks — and check that
each fits one chip's 16 GB.  Nothing runs: these tests only show what
the chip's compiler accepts.  They also pin the reason the fused kernel
is refused on a TPU.

The topology is described inside a fixture, never at import, so that
every test worker collects the same tests and only the worker given
this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine as eng
from repro.core.census import TPU_REFUSED, check_backend
from repro.core.planner import DESC_SEARCH_ITERS, num_desc_anchors
from repro.kernels import ops
from repro.kernels.tricode_hist import tricode_histogram_kernel

#: batch-phase shapes of ``chip_smoke.py`` at seed 0: the generated
#: cit-Patents-scale graph (3,774,768 vertices, 15,505,417 canonical
#: pairs, max degree 4,092) streamed in 4,194,304-item chunks under
#: ``orient="degree"``
N_INDPTR = 3_774_769
N_PACKED = 31_010_834
N_PAIRS = 15_505_417
CHUNK = 4_194_304
DESC_SHAPE = 176_199
SEARCH_ITERS = 12
#: megastep window cap (engine.MAX_WINDOWS_PER_DISPATCH)
K = eng.MAX_WINDOWS_PER_DISPATCH
#: one v5e chip's HBM
HBM_BYTES = 16 * 10**9

DESC_WORDS = 1 + 3 * DESC_SHAPE + num_desc_anchors(CHUNK)

#: shapes of the benchmark's ``patents-batch`` cell (the 1/16
#: cit-Patents graph: 235,923 vertices, 1,032,434 pairs, 177,683
#: descriptors a window) in the same 4,194,304-lane chunks
BATCH_N_INDPTR = 235_924
BATCH_N_PACKED = 2_064_868
BATCH_N_PAIRS = 1_032_434
BATCH_DESC_SHAPE = 177_683
BATCH_SEARCH_ITERS = 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def i32(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip)


@pytest.fixture(scope="module")
def graph(i32):
    """The resident CSR + pair arrays of the batch graph."""
    return (i32(N_INDPTR), i32(N_PACKED), i32(N_PAIRS), i32(N_PAIRS),
            i32(N_PAIRS))


def _fits_one_chip(compiled) -> None:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


def test_chunk_step_compiles(graph, i32):
    """Host emission: packed items through ``census_partials``."""
    compiled = eng._chunk_step.lower(
        *graph, i32(CHUNK), i32(CHUNK), None, SEARCH_ITERS,
        "jnp").compile()
    _fits_one_chip(compiled)


def test_desc_step_compiles(graph, i32):
    """Device emission: one descriptor window per dispatch."""
    compiled = eng._desc_step.lower(
        *graph, i32(DESC_WORDS), i32(CHUNK), None, SEARCH_ITERS,
        DESC_SEARCH_ITERS, "jnp", "degree", True).compile()
    _fits_one_chip(compiled)


#: one instruction of compiled HLO text: name, opcode, operands
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*? ([a-z][\w-]*)\(([^)]*)\)")


def _gather_tables(hlo: str, lanes: int) -> list[str]:
    """The table each ``lanes``-wide gather of a compiled module reads:
    the entry parameter it comes from, through fusion parameters and
    copies, else the instruction that makes it."""
    defs, caller, gathers, comp = {}, {}, [], None
    for line in hlo.splitlines():
        if not line.startswith(" "):
            comp = re.match(r"(?:ENTRY )?%([\w.-]+)", line)
            comp = comp and comp.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, op, args = m.groups()
        defs[name] = (comp, op, re.findall(r"%([\w.-]+)", args) or args)
        called = re.search(r"calls=%([\w.-]+)", line)
        if called:
            caller[called.group(1)] = name
        if op == "gather" and f"= s32[{lanes}]{{" in line:
            gathers.append(name)

    def source(name):
        comp, op, args = defs[name]
        if op == "parameter" and comp in caller:
            return source(defs[caller[comp]][2][int(args)])
        if op in ("copy-start", "copy-done", "copy", "bitcast"):
            return source(args[0])
        return name

    return [source(defs[g][2][0]) for g in gathers]


def test_desc_step_gathers_each_pair_field_once(topo):
    """At ``patents-batch``'s shapes the descriptor step reads each of
    the pair tables once per lane: ``expand`` gathers the pair fields
    and row bounds, and ``classify``/``keep`` take them from it."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    words = 1 + 3 * BATCH_DESC_SHAPE + num_desc_anchors(CHUNK)
    text = eng._desc_step.lower(
        i32(BATCH_N_INDPTR), i32(BATCH_N_PACKED), i32(BATCH_N_PAIRS),
        i32(BATCH_N_PAIRS), i32(BATCH_N_PAIRS), i32(words), i32(CHUNK),
        None, BATCH_SEARCH_ITERS, DESC_SEARCH_ITERS, "jnp", "degree",
        True).compile().as_text()
    tables = [t.split(".")[0] for t in _gather_tables(text, CHUNK)]
    assert 0 < len(tables) <= 28, tables
    for field in ("pair_u", "pair_v", "pair_code"):
        assert tables.count(field) == 1, tables


def test_megastep_compiles(graph, i32):
    """Async partitioned runs: K stacked windows scanned per dispatch."""
    compiled = eng._desc_megastep.lower(
        *graph, i32(K, DESC_WORDS), i32(CHUNK), SEARCH_ITERS,
        DESC_SEARCH_ITERS, "jnp", "degree", True).compile()
    _fits_one_chip(compiled)


def test_tricode_histogram_kernel_compiles(i32):
    compiled = tricode_histogram_kernel.lower(
        i32(CHUNK), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_backend_chunk_step_compiles(graph, i32, monkeypatch):
    """The ``pallas`` backend's chunk step with the Mosaic-compiled
    histogram kernel in it (the wrapper would pick interpret mode from
    this host's CPU backend)."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    compiled = eng._chunk_step.lower(
        *graph, i32(CHUNK), i32(CHUNK), None, SEARCH_ITERS,
        "pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_pallas_fused_refused_on_tpu(monkeypatch):
    with pytest.raises(ValueError, match="cannot run on a TPU"):
        check_backend("pallas-fused", "tpu")
    check_backend("pallas-fused", "cpu")
    check_backend("jnp", "tpu")
    check_backend("pallas", "tpu")
    with pytest.raises(ValueError, match="unknown backend"):
        check_backend("cuda", "tpu")
    # the engine refuses at construction, from its mesh's platform
    monkeypatch.setattr(eng, "_platform", lambda mesh=None: "tpu")
    with pytest.raises(ValueError, match="Only 2D gather"):
        eng.CensusEngine(backend="pallas-fused")
    eng.CensusEngine(backend="jnp")


def test_fused_kernel_still_refused_by_mosaic(i32, monkeypatch):
    """Keeps :data:`TPU_REFUSED` honest: the day Mosaic accepts the
    fused kernel, this fails and the refusal should go."""
    assert "pallas-fused" in TPU_REFUSED
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        eng._chunk_step.lower(
            i32(1025), i32(4096), i32(2048), i32(2048), i32(2048),
            i32(8192), i32(8192), None, 8, "pallas-fused").compile()
