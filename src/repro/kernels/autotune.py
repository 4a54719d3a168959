"""Tile-size autotuning for the Pallas aggregation kernels, and the
``KernelCostTable`` artifact that closes the sim-to-real loop.

The three aggregation kernels (``fused_agg``, ``pair_fuse``, ``quant_agg``)
are bandwidth-bound: their cost is the HBM bytes they move divided by the
chip's HBM bandwidth (``repro.launch.roofline.bandwidth_time_s`` /
``repro.launch.mesh.HardwareSpec``). Tile choice changes the bytes moved:

  * the fp32 output tile is **revisited on every K-grid step** — TPU grids
    iterate the last dimension innermost, so the (bn,) output block is
    fetched and written back once per ``kb``-slab of updates
    (``o_ref[...] +=``). A larger ``kb`` means fewer slabs and less
    read-modify-write traffic; ``kb >= K`` eliminates it entirely.
  * padding to the tile grid moves dead bytes: a huge ``bn`` on a small
    model wastes bandwidth on the padded tail.
  * VMEM is finite: the input tile (``kb * bn * itemsize``) must fit the
    per-core budget with room for pipelining (double buffering).

``autotune`` searches the legal (bn, kb) grid for one kernel x shape and
scores every candidate with the corrected bytes derivation
(``kernel_bytes_moved`` — the old ``benchmarks/kernel_bench.py`` model
ignored both the output RMW and padding). The search is exhaustive over a
few dozen candidates, deterministic, and interpret-mode-safe: it never has
to *run* the kernel to rank candidates.

``build_cost_table`` turns tuned configurations into a ``KernelCostTable``
mapping (kernel, model_bytes) -> t_pair seconds, the §5.4 quantity the
simulator prices fuse work with:

  * ``basis="roofline"`` (the CPU container default) projects t_pair from
    the bandwidth roofline at the tuned tile — what the kernel would cost
    on the target TPU. This is honest about what a CPU box can know.
  * ``basis="measured"`` additionally wall-clocks the compiled tuned kernel
    and records the measured median instead. It runs only on a TPU (it
    fails elsewhere) and stamps the table with the chip's ``device_kind``.
    The artifact records its basis so a consumer can tell projection from
    measurement.

``AggregationEstimator(cost_table=...)`` (and ``Platform(cost_table=...)``)
then source simulated t_pair/t_agg from the table instead of a config
constant; see ``repro.core.estimator``.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.launch.mesh import V5E, HardwareSpec
from repro.launch.roofline import bandwidth_time_s

#: fp32 VMEM tiles are (8, 128); the 1-D blocks in these kernels keep the
#: existing kernels' convention of bn as a multiple of 8 * 128 = 1024.
LANE_BLOCK = 1024
#: per-core VMEM budget for the working set (input tile + output tile,
#: double-buffered). The guide figure is ~16 MiB/core; leave half for the
#: compiler.
VMEM_BUDGET_BYTES = 8 << 20
#: modeled per-grid-step cost (DMA issue + pipeline bubble allowance).
#: Pure bytes/bandwidth cannot rank tile sizes on padding-free shapes —
#: every bn moves the same bytes — but small tiles issue many short DMAs
#: that underutilise HBM. ~100 ns/step makes the model prefer the largest
#: tile that fits VMEM without adding padding waste.
STEP_OVERHEAD_S = 1e-7


@dataclasses.dataclass(frozen=True)
class KernelShapeSpec:
    """Static tiling facts for one kernel (see the kernel docstrings)."""

    name: str
    in_itemsize: int  # bytes per update element
    out_itemsize: int  # bytes per output element (fp32 accumulator)
    kb_align: int  # sublane alignment for the K (update) axis
    kb_candidates: Tuple[int, ...]
    bn_candidates: Tuple[int, ...]
    out_rmw: bool  # output block revisited across the K grid
    default_bn: int
    default_kb: int


_BNS = (1024, 2048, 4096, 8192, 16384, 32768)

KERNELS: Dict[str, KernelShapeSpec] = {
    # fused_agg: (K, N) fp32/bf16 updates, fp32 (bn,) accumulator tile
    "fused_agg": KernelShapeSpec(
        name="fused_agg", in_itemsize=4, out_itemsize=4, kb_align=8,
        kb_candidates=(8, 16, 32, 64, 128), bn_candidates=_BNS,
        out_rmw=True, default_bn=2048, default_kb=8),
    # quant_agg: (K, N) int8 updates, int8 tiles are (32, 128)
    "quant_agg": KernelShapeSpec(
        name="quant_agg", in_itemsize=1, out_itemsize=4, kb_align=32,
        kb_candidates=(32, 64, 128, 256), bn_candidates=_BNS,
        out_rmw=True, default_bn=4096, default_kb=32),
    # pair_fuse: two (N,) inputs, one output, no K grid (kb is K=2 inputs)
    "pair_fuse": KernelShapeSpec(
        name="pair_fuse", in_itemsize=4, out_itemsize=4, kb_align=1,
        kb_candidates=(2,), bn_candidates=_BNS,
        out_rmw=False, default_bn=8192, default_kb=2),
}


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def kernel_bytes_moved(kernel: str, k: int, n: int, *,
                       bn: int, kb: int) -> int:
    """HBM bytes one kernel launch moves at tile (bn, kb) — the corrected
    derivation (the old kernel_bench model was ``(k*n + n) * itemsize``):

      inputs   padded grid, so padding tiles count (they are streamed)
      weights  one (kb,) fp32 slice per K step — consecutive N steps share
               the block index, so it is fetched once per K slab
      output   ``out_rmw`` kernels revisit the fp32 (bn,) block on every
               K step (TPU grids run the N dimension innermost, so
               revisits are never consecutive): the first visit writes,
               each of the remaining ``gk - 1`` visits reads AND writes.
    """
    spec = KERNELS[kernel]
    if kernel == "pair_fuse":
        np_ = _ceil_to(n, bn)
        # a + b in, weights (2 scalars, one fetch), out written once
        return 2 * np_ * spec.in_itemsize + 2 * 4 + np_ * spec.out_itemsize
    kp = _ceil_to(k, kb)
    np_ = _ceil_to(n, bn)
    gk = kp // kb
    in_bytes = kp * np_ * spec.in_itemsize
    weight_bytes = kp * 4
    out_bytes = np_ * spec.out_itemsize * (2 * gk - 1 if spec.out_rmw else 1)
    return in_bytes + weight_bytes + out_bytes


def vmem_working_set(kernel: str, *, bn: int, kb: int) -> int:
    """Double-buffered per-step VMEM residency at tile (bn, kb)."""
    spec = KERNELS[kernel]
    if kernel == "pair_fuse":
        return 2 * (2 * bn * spec.in_itemsize + bn * spec.out_itemsize)
    return 2 * (kb * bn * spec.in_itemsize + bn * spec.out_itemsize) + kb * 4


def grid_steps(kernel: str, k: int, n: int, *, bn: int, kb: int) -> int:
    """Total grid iterations one launch executes at tile (bn, kb)."""
    np_ = _ceil_to(max(n, 1), bn)
    if kernel == "pair_fuse":
        return np_ // bn
    kp = _ceil_to(max(k, 1), kb)
    return (kp // kb) * (np_ // bn)


def modeled_time_s(kernel: str, k: int, n: int, *, bn: int, kb: int,
                   hw: HardwareSpec = V5E) -> float:
    """The autotuner's scoring model: bandwidth roofline over the corrected
    bytes, plus a per-grid-step overhead allowance (STEP_OVERHEAD_S)."""
    bts = kernel_bytes_moved(kernel, k, n, bn=bn, kb=kb)
    steps = grid_steps(kernel, k, n, bn=bn, kb=kb)
    return bandwidth_time_s(bts, hw) + steps * STEP_OVERHEAD_S


@dataclasses.dataclass(frozen=True)
class TileChoice:
    kernel: str
    k: int
    n: int
    bn: int
    kb: int
    bytes_moved: int
    roofline_s: float  # bytes / hbm_bw at the scoring HardwareSpec
    modeled_s: float  # roofline_s + grid-step overhead (the score)


def candidates(kernel: str, k: int, n: int) -> List[Tuple[int, int]]:
    """Legal (bn, kb) pairs for one kernel x shape: alignment respected,
    VMEM budget honoured, no tile larger than the (padded) problem."""
    spec = KERNELS[kernel]
    out: List[Tuple[int, int]] = []
    max_bn = _ceil_to(max(n, 1), LANE_BLOCK)
    max_kb = _ceil_to(max(k, 1), spec.kb_align)
    for bn in spec.bn_candidates:
        if bn > max(max_bn, spec.bn_candidates[0]):
            continue
        for kb in spec.kb_candidates:
            if kb % spec.kb_align and spec.kb_align > 1:
                continue
            if kb > max(max_kb, spec.kb_candidates[0]):
                continue
            if vmem_working_set(kernel, bn=bn, kb=kb) > VMEM_BUDGET_BYTES:
                continue
            out.append((bn, kb))
    return out


def autotune(kernel: str, k: int, n: int,
             hw: HardwareSpec = V5E) -> TileChoice:
    """Pick the (bn, kb) minimising modeled execution time for one shape.

    Deterministic: ties break toward less padding, then the smaller tile
    (lower VMEM pressure). Interpret-mode-safe — scoring is closed-form,
    so tuning never executes the kernel (CPU containers tune the same
    tables a TPU host would)."""
    best: Optional[Tuple[Tuple[float, int, int, int], TileChoice]] = None
    for bn, kb in candidates(kernel, k, n):
        bts = kernel_bytes_moved(kernel, k, n, bn=bn, kb=kb)
        t = modeled_time_s(kernel, k, n, bn=bn, kb=kb, hw=hw)
        pad = _ceil_to(n, bn) - n
        key = (t, pad, bn, kb)
        if best is None or key < best[0]:
            best = (key, TileChoice(kernel, k, n, bn, kb, bts,
                                    bandwidth_time_s(bts, hw), t))
    assert best is not None, f"no legal tile for {kernel} k={k} n={n}"
    return best[1]


# --------------------------------------------------------------------------
# KernelCostTable: (kernel, model_bytes) -> measured/projected t_pair
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CostEntry:
    """One tuned measurement: fusing updates of ``model_bytes`` with
    ``kernel`` at tile (bn, kb) costs ``t_pair_s`` seconds per pair."""

    kernel: str
    model_bytes: int
    t_pair_s: float
    bn: int
    kb: int
    basis: str  # "roofline" (projected) | "measured" (TPU wall-clock)


@dataclasses.dataclass
class KernelCostTable:
    """Measured-hardware §5.4 cost model: t_pair by kernel and model size.

    ``t_pair(model_bytes)`` interpolates linearly in bytes between the
    table's sizes (fusion is bandwidth-bound, hence linear in bytes) and
    scales proportionally beyond either end. JSON round-trips via
    ``dump``/``load`` so a table tuned on the TPU host ships to the
    simulator as an artifact.
    """

    entries: List[CostEntry] = dataclasses.field(default_factory=list)
    hw: str = "tpu_v5e"

    #: the estimator prices the paper's PAIRWISE fusion operator
    DEFAULT_KERNEL = "pair_fuse"

    def kernels(self) -> List[str]:
        return sorted({e.kernel for e in self.entries})

    def _sorted(self, kernel: str) -> List[CostEntry]:
        rows = sorted((e for e in self.entries if e.kernel == kernel),
                      key=lambda e: e.model_bytes)
        if not rows:
            raise KeyError(
                f"cost table has no entries for kernel {kernel!r} "
                f"(has: {self.kernels()})")
        return rows

    def t_pair(self, model_bytes: int,
               kernel: str = DEFAULT_KERNEL) -> float:
        rows = self._sorted(kernel)
        mb = float(max(model_bytes, 1))
        if mb <= rows[0].model_bytes:
            return rows[0].t_pair_s * mb / rows[0].model_bytes
        if mb >= rows[-1].model_bytes:
            return rows[-1].t_pair_s * mb / rows[-1].model_bytes
        for lo, hi in zip(rows, rows[1:]):
            if lo.model_bytes <= mb <= hi.model_bytes:
                f = (mb - lo.model_bytes) / (hi.model_bytes - lo.model_bytes)
                return lo.t_pair_s + f * (hi.t_pair_s - lo.t_pair_s)
        raise AssertionError("unreachable")

    def tile(self, model_bytes: int,
             kernel: str = DEFAULT_KERNEL) -> Tuple[int, int]:
        """The tuned (bn, kb) of the nearest table size."""
        rows = self._sorted(kernel)
        e = min(rows, key=lambda e: abs(e.model_bytes - model_bytes))
        return e.bn, e.kb

    # ---- serialization ----------------------------------------------------
    def to_json(self) -> Dict:
        return {"hw": self.hw,
                "entries": [dataclasses.asdict(e) for e in self.entries]}

    @classmethod
    def from_json(cls, obj: Dict) -> "KernelCostTable":
        return cls(entries=[CostEntry(**e) for e in obj["entries"]],
                   hw=obj.get("hw", "tpu_v5e"))

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "KernelCostTable":
        with open(path) as f:
            return cls.from_json(json.load(f))


def _measure_pair_s(kernel: str, n_elems: int, bn: int, kb: int, *,
                    trials: int = 3) -> float:
    """Median wall-clock of one compiled tuned kernel launch, warmup
    blocked: on a TPU this IS the measured t_pair."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_agg import fused_agg
    from repro.kernels.pair_fuse import pair_fuse
    from repro.kernels.quant_agg import quant_agg

    key = jax.random.PRNGKey(0)
    if kernel == "pair_fuse":
        a = jax.random.normal(key, (n_elems,), jnp.float32)
        fn = lambda: pair_fuse(a, a, op="wsum", wa=0.5, wb=0.5,
                               bn=bn, interpret=False)
    elif kernel == "fused_agg":
        u = jax.random.normal(key, (kb, n_elems), jnp.float32)
        w = jnp.full((kb,), 1.0 / kb, jnp.float32)
        fn = lambda: fused_agg(u, w, bn=bn, kb=kb, interpret=False)
    elif kernel == "quant_agg":
        q = jax.random.randint(key, (kb, n_elems), -127, 128,
                               dtype=jnp.int8)
        s = jnp.full((kb,), 0.01, jnp.float32)
        fn = lambda: quant_agg(q, s, bn=bn, kb=kb, interpret=False)
    else:
        raise ValueError(kernel)
    jax.block_until_ready(fn())  # warmup: compile AND finish async work
    ts = []
    for _ in range(max(trials, 3)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    t = float(np.median(ts))
    if kernel == "fused_agg" or kernel == "quant_agg":
        # the launch fuses kb updates in one sweep: per-pair share
        return t / max(kb - 1, 1)
    return t


def build_cost_table(
    model_sizes_bytes: Sequence[int],
    kernels: Sequence[str] = ("pair_fuse", "fused_agg", "quant_agg"),
    *,
    basis: str = "roofline",
    hw: HardwareSpec = V5E,
    hw_name: str = "tpu_v5e",
) -> KernelCostTable:
    """Tune every (kernel, model size) and emit the cost-table artifact.

    ``basis="roofline"`` projects t_pair from the tuned tile's bandwidth
    roofline (what a CPU container can honestly say about the TPU target)
    and names the table ``hw_name``; ``basis="measured"`` wall-clocks the
    compiled tuned kernel on the TPU it runs on, fails without one, and
    names the table by that chip's ``device_kind``.
    """
    assert basis in ("roofline", "measured"), basis
    if basis == "measured":
        import jax

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise RuntimeError(
                f"basis='measured' times the compiled kernels on a TPU; "
                f"this process runs on {dev.platform!r}")
        hw_name = dev.device_kind
    entries: List[CostEntry] = []
    for kernel in kernels:
        spec = KERNELS[kernel]
        for mb in sorted(model_sizes_bytes):
            n = max(mb // spec.in_itemsize, 1)
            k = spec.default_kb if kernel != "pair_fuse" else 2
            choice = autotune(kernel, k, n, hw=hw)
            if basis == "measured":
                t_pair = _measure_pair_s(kernel, n, choice.bn, choice.kb)
            else:
                # per-pair share of one modeled launch at the tuned tile
                pairs = max(k - 1, 1) if kernel != "pair_fuse" else 1
                t_pair = choice.modeled_s / pairs
            entries.append(CostEntry(kernel=kernel, model_bytes=int(mb),
                                     t_pair_s=t_pair, bn=choice.bn,
                                     kb=choice.kb, basis=basis))
    return KernelCostTable(entries=entries, hw=hw_name)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes-mb", default="1,4,16,64,256",
                    help="comma-separated model sizes in MiB")
    ap.add_argument("--basis", choices=("roofline", "measured"),
                    default="roofline",
                    help="roofline: project from the tuned tile (CPU-safe);"
                         " measured: wall-clock the compiled kernels on a TPU")
    ap.add_argument("--out", default="kernel_cost_table.json")
    args = ap.parse_args()
    sizes = [int(float(s) * (1 << 20))
             for s in args.sizes_mb.split(",") if s]
    table = build_cost_table(sizes, basis=args.basis)
    table.dump(args.out)
    for e in table.entries:
        print(f"{e.kernel},{e.model_bytes},{e.t_pair_s:.3e},bn={e.bn},"
              f"kb={e.kb},{e.basis}")
    print(f"[wrote {args.out}: {len(table.entries)} entries]")


if __name__ == "__main__":
    main()
