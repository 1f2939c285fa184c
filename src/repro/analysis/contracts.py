"""Trace-time kernel contract checker (DESIGN.md §11).

Every Pallas kernel family in this repo follows the same halo-tiled shape:
a grid over (batch, spatial tiles, channel blocks, reduction sweep), input
``pl.Element`` BlockSpecs whose index maps read a halo-widened window from
a pre-padded array, and — when a grid dim revisits an output block —
an accumulation scratch in a widened dtype with the output written only on
the final visit. Each of those properties broke at least once in this
repo's history (the seed's out-of-bounds halo indexing is why PR 1
exists), so this module makes them *machine-checked contracts*: each
family registers a builder that reconstructs the kernel's launch geometry
(grid, block shapes, index maps, scratch) symbolically from the shape
parameters — mirroring the kernel code, importing its constants so the two
cannot drift on tile defaults — and the checker evaluates the declaration
over the autotune key space:

  * **halo_oob** — every index-mapped block stays inside its (padded)
    array for every grid point: ``pl.Element`` maps return *element*
    offsets on every axis, so ``offset + block_shape <= array_shape`` per
    axis (blocked maps return block indices, scaled by the block shape
    first).
  * **vmem_budget** — per-grid-instance working set: in/out blocks are
    double-buffered by the pipeline (×2) plus scratch, must fit the
    configurable budget (default 16 MB — one TPU core's VMEM). This is
    the verdict ``autotune`` consults to prune candidate tiles before
    timing them.
  * **acc_dtype** — accumulator widening: int8×int8 kernels must
    accumulate in int32; float kernels (incl. bf16 inputs) in float32.
  * **revisit_race** — any grid dim that revisits an accumulation block
    (the output's index map is constant along it) must (a) trail every
    varying dim — TPU grids execute rightmost-fastest, so a leading
    revisit dim would interleave other blocks' visits between two visits
    of the same accumulator — (b) not be declared "parallel", and (c) the
    output must be written only on the final visit.

Checks are pure Python over small integers — no tracing, no compilation —
so the full key space (fig1/fig2/conv1d shapes × autotune candidates ×
precisions) evaluates in seconds.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import os
from typing import Callable, Iterable, Iterator

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024  # one TPU core's VMEM, bytes

DTYPE_BYTES = {
    "int8": 1, "uint8": 1, "bool": 1,
    "bfloat16": 2, "float16": 2, "int16": 2,
    "float32": 4, "int32": 4,
    "float64": 8, "int64": 8,
}

#: grid points evaluated exhaustively below this; larger grids sample
#: per-dim {0, 1, mid, last-1, last} (index maps here are affine or
#: modulo-periodic with a period dividing the dim, so extremes at the
#: sampled corners are the true extremes)
GRID_EVAL_CAP = 50_000


def vmem_budget() -> int:
    """Configured VMEM budget in bytes (``REPRO_VMEM_BUDGET`` overrides)."""
    return int(os.environ.get("REPRO_VMEM_BUDGET", DEFAULT_VMEM_BUDGET))


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class Block:
    """One BlockSpec (or scratch buffer) of a kernel instance.

    ``index_map`` maps grid indices to offsets — *element* offsets when
    ``element`` (the ``pl.Element`` halo specs), block indices otherwise.
    Scratch buffers have no map and no backing array.
    """

    name: str
    shape: tuple[int, ...]
    dtype: str
    index_map: Callable[..., tuple] | None = None
    array_shape: tuple[int, ...] | None = None
    element: bool = False

    def nbytes(self) -> int:
        return math.prod(self.shape) * DTYPE_BYTES[self.dtype]


@dataclasses.dataclass(frozen=True)
class Violation:
    """One typed contract violation. ``kind`` is the machine-checkable
    class: halo_oob | vmem_budget | acc_dtype | revisit_race | bloat |
    chain_dequant | lint_*."""

    kind: str
    family: str
    key: str
    detail: str

    def line(self) -> str:
        return f"[{self.kind}] {self.family} {self.key}: {self.detail}"


@dataclasses.dataclass
class KernelInstance:
    """A kernel family's launch geometry at one concrete shape+tiling.

    ``compute_dtypes`` are the two contraction operand dtypes (decides the
    required accumulator); ``acc_dtype`` is the dtype accumulation
    actually happens in (revisit scratch dtype, or the in-register
    accumulator for single-visit kernels). ``dim_roles`` defaults to all
    "arbitrary" (sequential — the TPU default); a "parallel" declaration
    on a revisiting dim is a race. ``out_on_last_visit`` declares the
    ``pl.when(r == n_red - 1)`` store predicate.
    """

    family: str
    key: str
    grid: tuple[int, ...]
    inputs: list[Block]
    outputs: list[Block]
    scratch: list[Block]
    compute_dtypes: tuple[str, str]
    acc_dtype: str
    dim_roles: tuple[str, ...] | None = None
    out_on_last_visit: bool = True


# ---------------------------------------------------------------------------
# checker
# ---------------------------------------------------------------------------

def _grid_points(grid: tuple[int, ...]) -> Iterator[tuple[int, ...]]:
    if math.prod(grid) <= GRID_EVAL_CAP:
        yield from itertools.product(*(range(g) for g in grid))
        return
    axes = [
        sorted({0, 1, g // 2, g - 2, g - 1} & set(range(g))) for g in grid
    ]
    yield from itertools.product(*axes)


def _block_bounds_violation(
    inst: KernelInstance, blk: Block
) -> Violation | None:
    if blk.index_map is None or blk.array_shape is None:
        return None
    for idx in _grid_points(inst.grid):
        off = blk.index_map(*idx)
        if len(off) != len(blk.shape):
            return Violation(
                "halo_oob", inst.family, inst.key,
                f"{blk.name}: index map arity {len(off)} != "
                f"block rank {len(blk.shape)}",
            )
        for d, (o, bs, asz) in enumerate(zip(off, blk.shape, blk.array_shape)):
            lo = o if blk.element else o * bs
            if lo < 0 or lo + bs > asz:
                return Violation(
                    "halo_oob", inst.family, inst.key,
                    f"{blk.name}: grid point {idx} reads "
                    f"[{lo}, {lo + bs}) on axis {d} of array dim {asz}",
                )
    return None


def _vmem_bytes(inst: KernelInstance) -> int:
    io = sum(b.nbytes() for b in inst.inputs + inst.outputs)
    return 2 * io + sum(b.nbytes() for b in inst.scratch)


def _required_acc(compute_dtypes: tuple[str, str]) -> str:
    return "int32" if all(d == "int8" for d in compute_dtypes) else "float32"


def _revisit_dims(inst: KernelInstance, out: Block) -> list[int]:
    """Grid dims (of size > 1) along which ``out``'s index map is
    constant — i.e. dims that re-visit the same output block."""
    if out.index_map is None:
        return []
    base = tuple(0 for _ in inst.grid)
    ref = out.index_map(*base)
    rev = []
    for d, g in enumerate(inst.grid):
        if g <= 1:
            continue
        probes = sorted({1, g // 2, g - 1} & set(range(1, g)))
        if all(
            out.index_map(*(
                p if i == d else 0 for i, p in
                enumerate(base[:d] + (q,) + base[d + 1:])
            )) == ref
            for q in probes
            for p in [None]
        ):
            rev.append(d)
    return rev


def check_instance(
    inst: KernelInstance, *, budget: int | None = None
) -> list[Violation]:
    """All contract violations for one kernel instance."""
    budget = vmem_budget() if budget is None else budget
    vio: list[Violation] = []

    for blk in inst.inputs + inst.outputs:
        v = _block_bounds_violation(inst, blk)
        if v is not None:
            vio.append(v)

    nbytes = _vmem_bytes(inst)
    if nbytes > budget:
        vio.append(Violation(
            "vmem_budget", inst.family, inst.key,
            f"per-instance working set {nbytes} B "
            f"(2x in/out blocks + scratch) > budget {budget} B",
        ))

    req = _required_acc(inst.compute_dtypes)
    if inst.acc_dtype != req:
        vio.append(Violation(
            "acc_dtype", inst.family, inst.key,
            f"{inst.compute_dtypes[0]}x{inst.compute_dtypes[1]} must "
            f"accumulate in {req}, declared {inst.acc_dtype}",
        ))

    roles = inst.dim_roles or ("arbitrary",) * len(inst.grid)
    for out in inst.outputs:
        rev = _revisit_dims(inst, out)
        if not rev:
            continue
        varying = [
            d for d, g in enumerate(inst.grid) if g > 1 and d not in rev
        ]
        bad_order = [d for d in varying if d > min(rev)]
        if bad_order:
            vio.append(Violation(
                "revisit_race", inst.family, inst.key,
                f"{out.name}: revisit dim {min(rev)} precedes varying "
                f"dim(s) {bad_order} — the accumulator would be shared "
                f"across interleaved visits of different output blocks",
            ))
        par = [d for d in rev if roles[d] == "parallel"]
        if par:
            vio.append(Violation(
                "revisit_race", inst.family, inst.key,
                f"{out.name}: revisit dim(s) {par} declared parallel — "
                f"accumulation over a parallel dim races",
            ))
        if not inst.out_on_last_visit:
            vio.append(Violation(
                "revisit_race", inst.family, inst.key,
                f"{out.name}: output written on every visit of revisit "
                f"dim(s) {rev} instead of only the final one",
            ))
    return vio


# ---------------------------------------------------------------------------
# family builders — each mirrors ONE pallas_call's launch geometry,
# importing the kernel module's constants so defaults cannot drift
# ---------------------------------------------------------------------------

def _conv1d_geom(L, K, stride, tile_l, out_len):
    """(tile_l, n_tiles, padded_out, halo, padded input length) — the halo
    rounded to whole sublanes as ``sliding_conv1d.halo_input`` does."""
    from repro.kernels.sliding_conv1d import halo_rows

    tile_l = min(tile_l, out_len)
    n_tiles = _cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    halo = halo_rows((tile_l - 1) * stride + K)
    return (
        tile_l, n_tiles, padded_out, halo,
        max(L, (n_tiles - 1) * tile_l * stride + halo),
    )


def _phase_split(L, Cin, K, stride):
    """(L, Cin, K) of the stride-1 launch a stride-s multi-channel conv
    becomes (``sliding_conv1d.phase_split``/``phase_taps``)."""
    if stride == 1:
        return L, Cin, K
    return _cdiv(L, stride), stride * Cin, _cdiv(K, stride)


def build_conv1d(
    *, B, L, Cin, Cout, K, stride=1, precision="fp", dtype="float32",
    tile_l=None, cin_block=0, cout_block=0, regime=None,
) -> KernelInstance:
    """Contract for ``sliding_conv1d.conv1d_sliding_pallas`` (fp) and
    ``sliding_conv_quant.conv1d_quant_pallas`` (w8a8/w8a16). A stride
    above 1 is checked as the stride-1 launch over the phase-split input
    it runs as."""
    from repro.core.conv import regime_for
    from repro.kernels.sliding_conv1d import (
        DEFAULT_TILE_L, TAP_CHUNK, _resolve_block, halo_rows,
    )

    if (L - K) // stride + 1 < 1:
        raise ValueError(f"K={K} stride={stride} exceeds L={L}")
    key = f"conv1d|B{B}|L{L}|Cin{Cin}|Cout{Cout}|K{K}|s{stride}|{precision}"
    if regime is None:
        regime = "custom" if K in (3, 5) else regime_for(K)
    L, Cin, K = _phase_split(L, Cin, K, stride)
    out_len = L - K + 1
    tile_l, n_tiles, padded_out, halo, xlen = _conv1d_geom(
        L, K, 1, tile_l or DEFAULT_TILE_L, out_len
    )
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci, n_co = _cdiv(Cin, cb), _cdiv(Cout, ob)
    cin_p, cout_p = n_ci * cb, n_co * ob
    w8a8 = precision == "w8a8"
    xdt = "int8" if w8a8 else dtype
    wdt = "int8" if precision in ("w8a8", "w8a16") else dtype

    if regime == "compound":
        n_chunks = _cdiv(K, TAP_CHUNK)
        kp = n_chunks * TAP_CHUNK
        n_red = n_ci * n_chunks
        chunk_halo = halo_rows(tile_l - 1 + TAP_CHUNK)
        last = (n_tiles - 1) * tile_l + (n_chunks - 1) * TAP_CHUNK
        x_blk = Block(
            "x", (1, chunk_halo, cb), xdt,
            lambda b, i, co, r: (
                b,
                i * tile_l + (r % n_chunks) * TAP_CHUNK,
                (r // n_chunks) * cb,
            ),
            (B, max(L, last + chunk_halo), cin_p), element=True,
        )
        w_blk = Block(
            "w", (TAP_CHUNK, cb, ob), wdt,
            lambda b, i, co, r: (r % n_chunks, r // n_chunks, co),
            (kp, cin_p, cout_p),
        )
    else:
        n_red = n_ci
        x_blk = Block(
            "x", (1, halo, cb), xdt,
            lambda b, i, co, r: (b, i * tile_l, r * cb),
            (B, xlen, cin_p), element=True,
        )
        w_blk = Block(
            "w", (K, cb, ob), wdt,
            lambda b, i, co, r: (0, r, co), (K, cin_p, cout_p),
        )
    inputs = [x_blk, w_blk]
    row = lambda name: Block(  # noqa: E731 — (1, ob) epilogue rows
        name, (1, ob), "float32",
        lambda b, i, co, r: (0, co), (1, cout_p),
    )
    if precision != "fp":
        inputs += [row("scale"), row("bias")]
    else:
        inputs.append(row("bias"))
    acc = "int32" if w8a8 else "float32"
    out = Block(
        "out", (1, tile_l, ob), dtype,
        lambda b, i, co, r: (b, i, co), (B, padded_out, cout_p),
    )
    scratch = [] if n_red == 1 else [Block("acc", (tile_l, ob), acc)]
    return KernelInstance(
        family=f"conv1d.{precision}", key=key,
        grid=(B, n_tiles, n_co, n_red),
        inputs=inputs, outputs=[out], scratch=scratch,
        compute_dtypes=(xdt, "int8" if w8a8 else dtype), acc_dtype=acc,
    )


def build_conv2d(
    *, B, H, W, Cin, Cout, kh, kw, stride=(1, 1), precision="fp",
    dtype="float32", tile_h=None, tile_w=None, cin_block=0, cout_block=0,
    regime=None,
) -> KernelInstance:
    """Contract for ``sliding_conv2d.conv2d_sliding_pallas`` (fp) and
    ``sliding_conv_quant.conv2d_quant_pallas``."""
    from repro.core.conv import regime_for
    from repro.kernels.sliding_conv1d import _resolve_block, halo_rows
    from repro.kernels.sliding_conv2d import (
        DEFAULT_TILE_H, DEFAULT_TILE_W, ROW_CHUNK,
    )

    sh, sw = stride
    oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
    if oh < 1 or ow < 1:
        raise ValueError(f"filter ({kh},{kw}) exceeds input ({H},{W})")
    if regime is None:
        regime = (
            "custom" if (kh == kw and kh in (3, 5)) else regime_for(kw)
        )
    th = min(tile_h or DEFAULT_TILE_H, oh)
    tw = min(tile_w or DEFAULT_TILE_W, ow)
    nh, nw = _cdiv(oh, th), _cdiv(ow, tw)
    halo_h = (th - 1) * sh + kh
    halo_w = halo_rows((tw - 1) * sw + kw)
    hp = max(H, (nh - 1) * th * sh + halo_h)
    wp = max(W, (nw - 1) * tw * sw + halo_w)
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci, n_co = _cdiv(Cin, cb), _cdiv(Cout, ob)
    cin_p, cout_p = n_ci * cb, n_co * ob
    w8a8 = precision == "w8a8"
    xdt = "int8" if w8a8 else dtype
    wdt = "int8" if precision in ("w8a8", "w8a16") else dtype
    key = (
        f"conv2d|B{B}|H{H}|W{W}|Cin{Cin}|Cout{Cout}"
        f"|K{kh}x{kw}|s{sh}x{sw}|{precision}"
    )

    if regime == "compound":
        n_chunks = _cdiv(kh, ROW_CHUNK)
        khp = n_chunks * ROW_CHUNK
        n_red = n_ci * n_chunks
        chunk_halo_h = (th - 1) * sh + ROW_CHUNK
        last_h = (nh - 1) * th * sh + (n_chunks - 1) * ROW_CHUNK
        x_blk = Block(
            "x", (1, chunk_halo_h, halo_w, cb), xdt,
            lambda b, i, j, co, r: (
                b,
                i * th * sh + (r % n_chunks) * ROW_CHUNK,
                j * tw * sw,
                (r // n_chunks) * cb,
            ),
            (B, max(H, last_h + chunk_halo_h), wp, cin_p), element=True,
        )
        w_blk = Block(
            "w", (ROW_CHUNK, kw, cb, ob), wdt,
            lambda b, i, j, co, r: (r % n_chunks, 0, r // n_chunks, co),
            (khp, kw, cin_p, cout_p),
        )
    else:
        n_red = n_ci
        x_blk = Block(
            "x", (1, halo_h, halo_w, cb), xdt,
            lambda b, i, j, co, r: (b, i * th * sh, j * tw * sw, r * cb),
            (B, hp, wp, cin_p), element=True,
        )
        w_blk = Block(
            "w", (kh, kw, cb, ob), wdt,
            lambda b, i, j, co, r: (0, 0, r, co), (kh, kw, cin_p, cout_p),
        )
    inputs = [x_blk, w_blk]
    row = lambda name: Block(  # noqa: E731
        name, (1, ob), "float32",
        lambda b, i, j, co, r: (0, co), (1, cout_p),
    )
    inputs += [row("scale"), row("bias")] if precision != "fp" else [row("bias")]
    acc = "int32" if w8a8 else "float32"
    out = Block(
        "out", (1, th, tw, ob), dtype,
        lambda b, i, j, co, r: (b, i, j, co),
        (B, nh * th, nw * tw, cout_p),
    )
    scratch = [] if n_red == 1 else [Block("acc", (th * tw, ob), acc)]
    return KernelInstance(
        family=f"conv2d.{precision}", key=key,
        grid=(B, nh, nw, n_co, n_red),
        inputs=inputs, outputs=[out], scratch=scratch,
        compute_dtypes=(xdt, "int8" if w8a8 else dtype), acc_dtype=acc,
    )


def build_conv1d_depthwise(
    *, B, L, C, K, stride=1, precision="fp", dtype="float32",
    tile_l=None, c_block=0,
) -> KernelInstance:
    """Contract for ``conv1d_depthwise_pallas`` (fp) and
    ``conv1d_depthwise_quant_pallas`` — no reduction grid dim (channels
    are independent), per-tap VPU FMA accumulates in-register."""
    from repro.kernels.sliding_conv1d import DEFAULT_TILE_L, _resolve_block

    out_len = (L - K) // stride + 1
    if out_len < 1:
        raise ValueError(f"K={K} stride={stride} exceeds L={L}")
    tile_l, n_tiles, padded_out, halo, xlen = _conv1d_geom(
        L, K, stride, tile_l or DEFAULT_TILE_L, out_len
    )
    cb = _resolve_block(C, c_block)
    n_c = _cdiv(C, cb)
    cp = n_c * cb
    w8a8 = precision == "w8a8"
    xdt = "int8" if w8a8 else dtype
    wdt = "int8" if precision in ("w8a8", "w8a16") else dtype
    key = f"conv1ddw|B{B}|L{L}|C{C}|K{K}|s{stride}|{precision}"
    inputs = [
        Block(
            "x", (1, halo, cb), xdt,
            lambda b, i, c: (b, i * tile_l * stride, c * cb),
            (B, xlen, cp), element=True,
        ),
        Block("w", (K, cb), wdt, lambda b, i, c: (0, c), (K, cp)),
        Block(
            "bias", (1, cb), "float32", lambda b, i, c: (0, c), (1, cp)
        ),
    ]
    if precision != "fp":
        inputs.append(Block(
            "scale", (1, cb), "float32", lambda b, i, c: (0, c), (1, cp)
        ))
    out = Block(
        "out", (1, tile_l, cb), dtype,
        lambda b, i, c: (b, i, c), (B, padded_out, cp),
    )
    return KernelInstance(
        family=f"conv1d_depthwise.{precision}", key=key,
        grid=(B, n_tiles, n_c), inputs=inputs, outputs=[out], scratch=[],
        compute_dtypes=(xdt, "int8" if w8a8 else dtype),
        acc_dtype="int32" if w8a8 else "float32",
    )


def build_pool1d(
    *, B, L, C, window, dtype="float32", tile_l=None
) -> KernelInstance:
    """Contract for ``sliding_pool.sliding_pool_pallas`` — halo indexing
    with no reduction dim and no scratch."""
    from repro.kernels.sliding_conv1d import halo_rows
    from repro.kernels.sliding_pool import DEFAULT_TILE

    out_len = L - window + 1
    if out_len < 1:
        raise ValueError(f"window={window} exceeds L={L}")
    tile_l = min(tile_l or DEFAULT_TILE, out_len)
    n_tiles = _cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    halo = halo_rows(tile_l + window - 1)
    key = f"pool1d|B{B}|L{L}|C{C}|w{window}|{dtype}"
    inputs = [Block(
        "x", (1, halo, C), dtype,
        lambda b, i: (b, i * tile_l, 0),
        (B, max(L, (n_tiles - 1) * tile_l + halo), C), element=True,
    )]
    out = Block(
        "out", (1, tile_l, C), dtype,
        lambda b, i: (b, i, 0), (B, padded_out, C),
    )
    return KernelInstance(
        family="pool1d", key=key, grid=(B, n_tiles),
        inputs=inputs, outputs=[out], scratch=[],
        compute_dtypes=(dtype, dtype), acc_dtype="float32",
    )


def build_conv1d_bwd_dw(
    *, B, L, Cin, Cout, K, stride=1, dtype="float32", tile_l=None,
    cin_block=0, cout_block=0,
) -> KernelInstance:
    """Contract for ``sliding_conv_bwd.conv1d_bwd_dw_pallas`` — the dw
    reduction: output (the weight gradient) indexed by the LEADING channel
    dims, reduction over trailing (batch, tile) dims into f32 scratch. A
    stride above 1 is checked as its stride-1 launch over the phase-split
    input."""
    from repro.kernels.sliding_conv1d import DEFAULT_TILE_L, _resolve_block

    out_len = (L - K) // stride + 1
    if out_len < 1:
        raise ValueError(f"K={K} stride={stride} exceeds L={L}")
    key = f"conv1d|B{B}|L{L}|Cin{Cin}|Cout{Cout}|K{K}|s{stride}|{dtype}|grad"
    L, Cin, K = _phase_split(L, Cin, K, stride)
    tile_l, n_tiles, padded_out, halo, xlen = _conv1d_geom(
        L, K, 1, tile_l or DEFAULT_TILE_L, out_len
    )
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci, n_co = _cdiv(Cin, cb), _cdiv(Cout, ob)
    cin_p, cout_p = n_ci * cb, n_co * ob
    inputs = [
        Block(
            "x", (1, halo, cb), dtype,
            lambda co, ci, b, i: (b, i * tile_l, ci * cb),
            (B, xlen, cin_p), element=True,
        ),
        Block(
            "dz", (1, tile_l, ob), dtype,
            lambda co, ci, b, i: (b, i, co), (B, padded_out, cout_p),
        ),
    ]
    dw = Block(
        "dw", (K, cb, ob), dtype,
        lambda co, ci, b, i: (0, ci, co), (K, cin_p, cout_p),
    )
    db = Block(
        "db", (1, ob), dtype,
        lambda co, ci, b, i: (0, co), (1, cout_p),
    )
    scratch = [
        Block("dw_acc", (K, cb, ob), "float32"),
        Block("db_acc", (1, ob), "float32"),
    ]
    return KernelInstance(
        family="conv1d_bwd_dw", key=key,
        grid=(n_co, n_ci, B, n_tiles),
        inputs=inputs, outputs=[dw, db], scratch=scratch,
        compute_dtypes=(dtype, dtype), acc_dtype="float32",
    )


def build_conv2d_bwd_dw(
    *, B, H, W, Cin, Cout, kh, kw, stride=(1, 1), dtype="float32",
    tile_h=None, tile_w=None, cin_block=0, cout_block=0,
) -> KernelInstance:
    """Contract for ``sliding_conv_bwd.conv2d_bwd_dw_pallas``."""
    from repro.kernels.sliding_conv1d import _resolve_block, halo_rows
    from repro.kernels.sliding_conv2d import DEFAULT_TILE_H, DEFAULT_TILE_W

    sh, sw = stride
    oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
    if oh < 1 or ow < 1:
        raise ValueError(f"filter ({kh},{kw}) exceeds input ({H},{W})")
    th = min(tile_h or DEFAULT_TILE_H, oh)
    tw = min(tile_w or DEFAULT_TILE_W, ow)
    nh, nw = _cdiv(oh, th), _cdiv(ow, tw)
    halo_h, halo_w = (th - 1) * sh + kh, halo_rows((tw - 1) * sw + kw)
    hp = max(H, (nh - 1) * th * sh + halo_h)
    wp = max(W, (nw - 1) * tw * sw + halo_w)
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci, n_co = _cdiv(Cin, cb), _cdiv(Cout, ob)
    cin_p, cout_p = n_ci * cb, n_co * ob
    key = (
        f"conv2d|B{B}|H{H}|W{W}|Cin{Cin}|Cout{Cout}"
        f"|K{kh}x{kw}|s{sh}x{sw}|{dtype}|grad"
    )
    inputs = [
        Block(
            "x", (1, halo_h, halo_w, cb), dtype,
            lambda co, ci, b, i, j: (b, i * th * sh, j * tw * sw, ci * cb),
            (B, hp, wp, cin_p), element=True,
        ),
        Block(
            "dz", (1, th, tw, ob), dtype,
            lambda co, ci, b, i, j: (b, i, j, co),
            (B, nh * th, nw * tw, cout_p),
        ),
    ]
    dw = Block(
        "dw", (kh, kw, cb, ob), dtype,
        lambda co, ci, b, i, j: (0, 0, ci, co), (kh, kw, cin_p, cout_p),
    )
    db = Block(
        "db", (1, ob), dtype,
        lambda co, ci, b, i, j: (0, co), (1, cout_p),
    )
    scratch = [
        Block("dw_acc", (kh, kw, cb, ob), "float32"),
        Block("db_acc", (1, ob), "float32"),
    ]
    return KernelInstance(
        family="conv2d_bwd_dw", key=key,
        grid=(n_co, n_ci, B, nh, nw),
        inputs=inputs, outputs=[dw, db], scratch=scratch,
        compute_dtypes=(dtype, dtype), acc_dtype="float32",
    )


def build_conv1d_depthwise_bwd_dw(
    *, B, L, C, K, stride=1, dtype="float32", tile_l=None, c_block=0
) -> KernelInstance:
    """Contract for ``conv1d_depthwise_bwd_dw_pallas``."""
    from repro.kernels.sliding_conv1d import DEFAULT_TILE_L, _resolve_block

    out_len = (L - K) // stride + 1
    if out_len < 1:
        raise ValueError(f"K={K} stride={stride} exceeds L={L}")
    tile_l, n_tiles, padded_out, halo, xlen = _conv1d_geom(
        L, K, stride, tile_l or DEFAULT_TILE_L, out_len
    )
    cb = _resolve_block(C, c_block)
    n_c = _cdiv(C, cb)
    cp = n_c * cb
    key = f"conv1ddw|B{B}|L{L}|C{C}|K{K}|s{stride}|{dtype}|grad"
    inputs = [
        Block(
            "x", (1, halo, cb), dtype,
            lambda c, b, i: (b, i * tile_l * stride, c * cb),
            (B, xlen, cp), element=True,
        ),
        Block(
            "dz", (1, tile_l, cb), dtype,
            lambda c, b, i: (b, i, c), (B, padded_out, cp),
        ),
    ]
    dw = Block("dw", (K, cb), dtype, lambda c, b, i: (0, c), (K, cp))
    return KernelInstance(
        family="conv1d_depthwise_bwd_dw", key=key,
        grid=(n_c, B, n_tiles),
        inputs=inputs, outputs=[dw],
        scratch=[Block("dw_acc", (K, cb), "float32")],
        compute_dtypes=(dtype, dtype), acc_dtype="float32",
    )


def build_attention_decode(
    *, B, S, KV, G, D, kind="int8", block_s=None, h_block=None
) -> KernelInstance:
    """Contract for ``attention_decode.decode_attention_pallas`` — the
    flash-style single-query read: kv_seq is the trailing sequential
    revisit dim over (m, l, o) f32 online-softmax scratches. ``lengths``
    is scalar-prefetched to SMEM, so it has no VMEM block."""
    from repro.kernels.attention_decode import DEFAULT_BLOCK_S, head_block

    bs = min(block_s or DEFAULT_BLOCK_S, S)
    n_s = _cdiv(S, bs)
    sp = n_s * bs
    hb = head_block(h_block, KV, D)
    n_h = KV // hb
    quantized = kind == "int8"
    kvdt = "int8" if quantized else kind
    key = f"attn_dec|B{B}|S{S}|KV{KV}|G{G}|D{D}|{kind}"
    inputs = [
        Block(
            "q", (1, hb, G, D), "float32",
            lambda b, h, s: (b, h, 0, 0), (B, KV, G, D),
        ),
        Block(
            "k", (1, bs, hb * D), kvdt,
            lambda b, h, s: (b, s, h), (B, sp, KV * D),
        ),
        Block(
            "v", (1, bs, hb * D), kvdt,
            lambda b, h, s: (b, s, h), (B, sp, KV * D),
        ),
    ]
    if quantized:
        for nm in ("k_scale", "v_scale"):
            inputs.append(Block(
                nm, (1, KV, bs), "float32",
                lambda b, h, s: (b, 0, s), (B, KV, sp),
            ))
    out = Block(
        "out", (1, hb, G, D), "float32",
        lambda b, h, s: (b, h, 0, 0), (B, KV, G, D),
    )
    scratch = [
        Block("m", (hb, G), "float32"),
        Block("l", (hb, G), "float32"),
        Block("o", (hb, G, D), "float32"),
    ]
    return KernelInstance(
        family=f"attention_decode.{kind}", key=key,
        grid=(B, n_h, n_s),
        inputs=inputs, outputs=[out], scratch=scratch,
        compute_dtypes=("float32", kvdt), acc_dtype="float32",
    )


def build_ssm_scan(
    *, B, L, D, N, dtype="float32", tile_d=None, chunk_l=None
) -> KernelInstance:
    """Contract for ``ssm_scan.ssm_scan_pallas`` — chunked recurrence:
    the L-chunk grid dim is the trailing sequential dim carrying the
    hidden state through f32 scratch; ``h_last`` writes on the final
    chunk only."""
    from repro.kernels.ssm_scan import DEFAULT_CHUNK_L, DEFAULT_TILE_D

    td = min(tile_d or DEFAULT_TILE_D, D)
    cl = min(chunk_l or DEFAULT_CHUNK_L, L)
    nd, nl = _cdiv(D, td), _cdiv(L, cl)
    dp, lp = nd * td, nl * cl
    key = f"ssm|B{B}|L{L}|D{D}|N{N}|{dtype}"
    seq = lambda nm: Block(  # noqa: E731 — (B, Lp, Dp, N) operands
        nm, (1, cl, td, N), dtype,
        lambda b, d, l: (b, l, d, 0), (B, lp, dp, N),
    )
    inputs = [
        seq("abar"),
        seq("bx"),
        Block(
            "c", (1, cl, N), dtype,
            lambda b, d, l: (b, l, 0), (B, lp, N),
        ),
        Block(
            "h0", (1, td, N), dtype,
            lambda b, d, l: (b, d, 0), (B, dp, N),
        ),
    ]
    y = Block(
        "y", (1, cl, td), dtype,
        lambda b, d, l: (b, l, d), (B, lp, dp),
    )
    h_last = Block(
        "h_last", (1, td, N), dtype,
        lambda b, d, l: (b, d, 0), (B, dp, N),
    )
    return KernelInstance(
        family="ssm_scan", key=key, grid=(B, nd, nl),
        inputs=inputs, outputs=[y, h_last],
        scratch=[Block("h", (td, N), "float32")],
        compute_dtypes=(dtype, dtype), acc_dtype="float32",
    )


#: family name → builder. Autotune candidate dicts (tile_l/cin_block/…)
#: splat straight into these alongside the shape parameters.
FAMILIES: dict[str, Callable[..., KernelInstance]] = {
    "conv1d": build_conv1d,
    "conv2d": build_conv2d,
    "conv1d_depthwise": build_conv1d_depthwise,
    "pool1d": build_pool1d,
    "conv1d_bwd_dw": build_conv1d_bwd_dw,
    "conv2d_bwd_dw": build_conv2d_bwd_dw,
    "conv1d_depthwise_bwd_dw": build_conv1d_depthwise_bwd_dw,
    "attention_decode": build_attention_decode,
    "ssm_scan": build_ssm_scan,
}


def check_autotune_candidate(
    family: str, shape: dict, cand: dict, *, budget: int | None = None
) -> Violation | None:
    """First contract violation for one autotune candidate, or None.

    This is the hook ``repro.kernels.autotune`` calls before timing a
    candidate: a tile that provably cannot fit VMEM (or indexes out of
    bounds) is pruned from the search instead of being measured. Unknown
    families and candidate keys the builder doesn't model return None —
    the search must degrade to measuring, never crash.
    """
    builder = FAMILIES.get(family)
    if builder is None:
        return None
    try:
        inst = builder(**shape, **cand)
        vio = check_instance(inst, budget=budget)
    except (TypeError, ValueError):
        return None
    return vio[0] if vio else None


# ---------------------------------------------------------------------------
# key space — the shapes CI proves the contracts over (mirrors the
# benchmarks: fig1 128²/32ch, fig2 96²/32ch, the conv1d 16384/32ch table,
# the qwen3 serving cache, the jamba ssm shapes)
# ---------------------------------------------------------------------------

FIG1 = dict(H=128, W=128, C=32, ks=(2, 3, 4, 5, 7, 9, 11, 13, 17, 19, 23, 27, 31))
FIG2 = dict(H=96, W=96, C=32, ks=(3, 5, 9, 13, 17, 25, 31))
CONV1D = dict(L=16384, C=32, ks=(2, 3, 5, 9, 17, 33, 65))
ATTN = dict(B=2, S=2048, KV=2, G=2, D=32)
SSM = dict(B=2, L=512, D=1024, N=16)


def default_space(quick: bool = False) -> Iterator[tuple[str, dict, dict]]:
    """(family, shape, candidate) triples covering every registered
    family × the benchmark shape keys × the autotune candidate space."""
    from repro.kernels import autotune as at
    from repro.kernels.attention_decode import BLOCK_S_CANDIDATES

    def blocks(c):
        return [b for b in at.CHANNEL_BLOCKS if b == 0 or b < c]

    figs = [FIG1] if quick else [FIG1, FIG2]
    for fig in figs:
        h, c = fig["H"], fig["C"]
        ks = fig["ks"][:3] if quick else fig["ks"]
        for k in ks:
            shape = dict(B=1, H=h, W=h, Cin=c, Cout=c, kh=k, kw=k)
            for prec in ("fp", "w8a8", "w8a16"):
                for th, tw in at.TILE_HW_CANDIDATES:
                    for ci in blocks(c):
                        for co in blocks(c):
                            yield "conv2d", dict(shape, precision=prec), {
                                "tile_h": th, "tile_w": tw,
                                "cin_block": ci, "cout_block": co,
                            }
            for th, tw in at.TILE_HW_CANDIDATES:
                yield "conv2d_bwd_dw", dict(shape), {
                    "tile_h": th, "tile_w": tw,
                }
    L, c = CONV1D["L"], CONV1D["C"]
    ks = CONV1D["ks"][:3] if quick else CONV1D["ks"]
    for k in ks:
        shape = dict(B=1, L=L, Cin=c, Cout=c, K=k)
        for prec in ("fp", "w8a8", "w8a16"):
            for t in at.TILE_L_CANDIDATES:
                for ci in blocks(c):
                    for co in blocks(c):
                        yield "conv1d", dict(shape, precision=prec), {
                            "tile_l": t, "cin_block": ci, "cout_block": co,
                        }
        for t in at.TILE_L_CANDIDATES:
            yield "conv1d_bwd_dw", dict(shape), {"tile_l": t}
    # depthwise (the mamba conv path) + its backward
    for prec in ("fp", "w8a8"):
        for t in at.TILE_L_CANDIDATES:
            for cbk in blocks(512):
                yield "conv1d_depthwise", dict(
                    B=2, L=4096, C=512, K=4, precision=prec
                ), {"tile_l": t, "c_block": cbk}
    yield "conv1d_depthwise_bwd_dw", dict(B=2, L=4096, C=512, K=4), {}
    for wdw in (4, 16, 64, 256):
        yield "pool1d", dict(B=1, L=16384, C=32, window=wdw), {}
    for kind in ("int8", "float32"):
        for bs in sorted(set(BLOCK_S_CANDIDATES) | {ATTN["S"]}):
            for hb in (1, ATTN["KV"]):
                yield "attention_decode", dict(ATTN, kind=kind), {
                    "block_s": bs, "h_block": hb,
                }
    yield "ssm_scan", dict(SSM), {}


def check_all(
    *, quick: bool = False, budget: int | None = None
) -> tuple[list[Violation], dict]:
    """Evaluate every registered family over the key space. Returns
    (violations, stats)."""
    budget = vmem_budget() if budget is None else budget
    violations: list[Violation] = []
    checked = 0
    families: set[str] = set()
    for family, shape, cand in default_space(quick=quick):
        inst = FAMILIES[family](**shape, **cand)
        families.add(inst.family)
        checked += 1
        violations.extend(check_instance(inst, budget=budget))
    stats = {
        "instances": checked,
        "families": sorted(families),
        "vmem_budget": budget,
    }
    return violations, stats
