"""Compile the three fusion kernels, and the streaming aggregator's fold and
finish programs, for one TPU v5e chip, without a chip.

The TPU compiler is installed with JAX and compiles for a described
topology, so these tests refuse what the chip's compiler would refuse
(unaligned blocks, unsupported in-kernel ops, too much VMEM or HBM) at no
chip time. Sizes: the paper's §6.3 update sizes (`benchmarks/workloads.py`)
at the tiles `repro.kernels.autotune` picks, and every autotuned row of
`benchmarks/kernel_baseline.json`. Each compiled kernel program must
contain the Pallas kernel as a `tpu_custom_call`, i.e. nothing fell back to
the interpreter; the streaming fold holds no kernel.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold.
"""
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import pytest

from benchmarks.workloads import WORKLOADS
from repro.fl.fusion import finished_model, get_algorithm
from repro.kernels import SlabAcc, slab_plan
from repro.kernels import autotune as at
from repro.kernels.ops import first_fold, fold_into
from repro.kernels.fused_agg import fused_agg
from repro.kernels.pair_fuse import pair_fuse
from repro.kernels.quant_agg import quant_agg

#: one v5e chip's HBM
HBM_BYTES = 16 * 2**30
#: updates per batch drain at the §6.3 sizes
K_FUSED, K_QUANT = 8, 64

_ROOT = pathlib.Path(__file__).resolve().parents[1]
_BASELINE = _ROOT / "benchmarks" / "kernel_baseline.json"


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, kernel: str, k: int, n: int, bn: int, kb: int):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kernel == "pair_fuse":
        fn = lambda a, b: pair_fuse(a, b, op="wsum", wa=1.0, wb=0.25, bn=bn,
                                    interpret=False)
        args = (arg((n,), jnp.float32), arg((n,), jnp.float32))
    elif kernel == "fused_agg":
        fn = lambda u, w: fused_agg(u, w, bn=bn, kb=kb, interpret=False)
        args = (arg((k, n), jnp.float32), arg((k,), jnp.float32))
    else:
        fn = lambda q, s: quant_agg(q, s, bn=bn, kb=kb, interpret=False)
        args = (arg((k, n), jnp.int8), arg((k,), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, (kernel, k, n, total)
    return compiled


@pytest.mark.parametrize("wl", WORKLOADS, ids=lambda w: w.name)
@pytest.mark.parametrize("kernel,k", [
    ("pair_fuse", 2), ("fused_agg", K_FUSED), ("quant_agg", K_QUANT)])
def test_kernel_compiles_at_paper_size(one_chip, kernel, k, wl):
    n = wl.model_bytes // 4  # fp32 parameters
    tile = at.autotune(kernel, k, n)
    _compile(one_chip, kernel, k, n, tile.bn, tile.kb)


def _baseline_rows():
    rows = json.loads(_BASELINE.read_text())["model_rows"]
    return [pytest.param(r, id=f"{r['kernel']}-k{r['k']}-n{r['n']}")
            for r in rows]


@pytest.mark.parametrize("row", _baseline_rows())
@pytest.mark.parametrize("tile", ["default", "tuned"])
def test_kernel_compiles_at_baseline_tile(one_chip, row, tile):
    _compile(one_chip, row["kernel"], row["k"], row["n"],
             row[f"{tile}_bn"], row[f"{tile}_kb"])


def test_bf16_updates_compile(one_chip):
    """Model updates arrive in the model's dtype (bf16): the batch drain
    upcasts in the kernel."""
    n = 1 << 22
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tile = at.autotune("fused_agg", K_FUSED, n)
    compiled = jax.jit(
        lambda u, w: fused_agg(u, w, bn=tile.bn, kb=tile.kb, interpret=False)
    ).lower(arg((K_FUSED, n), jnp.bfloat16), arg((K_FUSED,), jnp.float32)
            ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1, 17, 128, 129, 500, 1000, 1025, 40_000])
@pytest.mark.parametrize("kernel,k", [("pair_fuse", 2), ("fused_agg", 3),
                                      ("quant_agg", 5)])
def test_kernel_compiles_for_short_leaves(one_chip, kernel, k, n):
    """A model's leaves (biases, norm scales) are far shorter than a tile:
    each kernel must still compile with its default tile."""
    spec = at.KERNELS[kernel]
    _compile(one_chip, kernel, k, n, spec.default_bn, spec.default_kb)


@pytest.mark.parametrize("config", ["vgg16", "effnetb7", "kanana2-30b"])
def test_fold_and_finish_compile_at_benchmark_leaves(one_chip, config):
    """The served path's three programs at the leaf lists of
    ``chipbench/configs`` (32, 711 and 73 leaves; the last bf16): the first
    fold, a fold (one multiply-add per slab, no kernel, every output
    written into the donated fp32 accumulator, no staging buffer beyond
    the packed slab's leaves) and a round's finish, which publishes the
    model in its own dtype."""
    cfg = json.loads((_ROOT / "chipbench" / "configs" /
                      f"{config}.json").read_text())
    f32 = jnp.float32
    arg = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=one_chip)
    shapes = tuple(tuple(s) for _, s in cfg["leaves"])
    dtype = jnp.dtype(cfg["dtype"])
    leaves = [arg(s, dtype) for s in shapes]
    n = sum(math.prod(s) for s in shapes)
    assert n == cfg["n_params"]
    plan = slab_plan(shapes)
    n_packed = sum(math.prod(shapes[i]) for i in plan.packed)

    first = first_fold.lower(leaves, arg(())).compile()
    assert first.memory_analysis().output_size_in_bytes >= 4 * n
    slabs = [arg(s.shape) for s in jax.eval_shape(first_fold, leaves,
                                                  arg(()))]
    assert len(slabs) == plan.n_slabs

    acc = SlabAcc(slabs, jax.tree.structure(leaves), plan)
    fold = fold_into.lower(acc, leaves, arg(())).compile()
    assert "tpu_custom_call" not in fold.as_text()
    # every output is one of the donated accumulator's buffers
    header = fold.as_text().split("\n", 1)[0]
    assert header.count("-alias)") == plan.n_slabs
    mem = fold.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * n
    assert mem.temp_size_in_bytes <= 4 * n_packed

    finish = finished_model.lower(
        get_algorithm(cfg["algorithm"]), acc, arg(()), leaves,
        arg(())).compile()
    mem = finish.memory_analysis()
    assert mem.output_size_in_bytes >= dtype.itemsize * n
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes < HBM_BYTES)
