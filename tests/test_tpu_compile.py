"""The refine kernels compiled for a described TPU v5e at real widths.

Nothing runs: the TPU compiler, installed with JAX, compiles for a
chip that is described and not attached, and refuses what the chip
would refuse — blocks off the (8, 128) tiling, more VMEM or SMEM than
the chip has, lane slices or scalar reads the lowering cannot express.
Interpret-mode tests (test_kernels.py) check the kernels' answers;
these check that the chip takes them.

The topology is described inside a module fixture, never at import
time: only one process at a time may load the TPU library, so every
test here runs in the process of the worker that is given this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitmap_refine import (refine_bitmap_rows,
                                         refine_bitmap_rows_hier)
from repro.kernels.config import (DEFAULT_CHUNK_WORDS,
                                  HBM_ADJACENCY_MIN_VERTICES)
from repro.roofline.analysis import PEAKS, chip_peaks
from repro.tuning.space import CandidateConfig, TunableSpace, \
    WorkloadShape

N_PAD = 64                      # query positions per wave row


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from a
    # persistent cache, so keep it off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def widest_wave(n_vertices: int, hbm_adjacency: int) -> int:
    """The widest wave the tuner may pick at this graph size."""
    cfg = CandidateConfig(hbm_adjacency=hbm_adjacency)
    fixed = {k: [v] for k, v in cfg.as_params().items()
             if k != "wave_size"}
    space = TunableSpace("pallas", WorkloadShape.for_graph(n_vertices))
    return max(c.wave_size for c in space.candidates(fixed))


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the kernel is in the program


def compile_dense(one_chip, v: int, f: int):
    w = (v + 31) // 32
    _compile(lambda adj, cand, fr, act: refine_bitmap_rows(
        adj, cand, fr, act, interpret=False, block_f=8), one_chip,
        ((v, w), jnp.uint32), ((f, w), jnp.uint32),
        ((f, N_PAD), jnp.int32), ((f, N_PAD), jnp.int32))


def compile_hier(one_chip, v: int, f: int, c: int = DEFAULT_CHUNK_WORDS):
    w = (v + 31) // 32
    sw = ((w + c - 1) // c + 31) // 32
    p = 6 * v                   # stored chunks: ~degree per vertex
    _compile(lambda s, cp, ci, cd, cand, fr, act: refine_bitmap_rows_hier(
        s, cp, ci, cd, cand, fr, act, interpret=False, dma_depth=2),
        one_chip, ((v, sw), jnp.uint32), ((v + 1,), jnp.int32),
        ((p,), jnp.int32), ((p, c), jnp.uint32), ((f, w), jnp.uint32),
        ((f, N_PAD), jnp.int32), ((f, N_PAD), jnp.int32))


@pytest.mark.parametrize("wave", ["default", "widest"])
def test_dense_kernel_compiles_at_threshold(one_chip, wave):
    """The largest graph the threshold sends to the dense kernel: its
    whole adjacency block must fit the chip's VMEM."""
    v = HBM_ADJACENCY_MIN_VERTICES - 1
    f = (CandidateConfig().wave_size if wave == "default"
         else widest_wave(v, hbm_adjacency=0))
    compile_dense(one_chip, v, f)


@pytest.mark.parametrize("v", [65_536, 1_048_576])
@pytest.mark.parametrize("wave", ["default", "widest"])
def test_hier_kernel_compiles(one_chip, v, wave):
    f = (CandidateConfig().wave_size if wave == "default"
         else widest_wave(v, hbm_adjacency=1))
    compile_hier(one_chip, v, f)


def test_dense_kernel_refused_past_vmem(one_chip):
    """The dense layout's ceiling is real: twice the threshold's graph
    (a 128 MiB block) does not fit, which is why larger graphs go to
    the hier kernel."""
    with pytest.raises(Exception, match="vmem"):
        compile_dense(one_chip, 2 * HBM_ADJACENCY_MIN_VERTICES, 512)


def test_described_chip_has_published_peaks(topo):
    kind = topo.devices[0].device_kind
    assert chip_peaks(kind) is PEAKS[kind]


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v0 imaginary")
