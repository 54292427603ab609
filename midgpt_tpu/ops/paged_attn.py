"""Pallas TPU paged-attention kernels for serving decode/verify.

The XLA paged-attention path (models.gpt.decode_paged_at /
verify_paged_at) reads the KV pool through a block-table gather:
``jnp.take(pool[layer], bt)`` materializes a ``[S, Pmax, Hkv, C, PS]``
intermediate in HBM, which the attention contraction then reads again.
These kernels read each slot's pages straight through its block table:
the grid runs over (slot x KV head), every block-table column is one
``BlockSpec`` whose index map looks the page id up in the
scalar-prefetched table, and the Pallas pipeline brings the pages of
the next grid step in behind the compute of this one. Nothing
page-shaped lands back in HBM, and the joint softmax + weighted-value
contraction run out of VMEM.

WHAT THE CHIP'S COMPILER ACCEPTS (PR 21 — the earlier in-kernel DMA
walk had only ever run interpreted): a pool page is ``[C, PS=16]``,
narrower than a 128-lane tile, and Mosaic refuses every manual DMA
slice of such an array; a block spanning the array's last two dims
whole is the one window it takes. So all ``Pmax`` pages of a (slot,
head) are VMEM-resident per grid step — each padded out to a lane tile
and double-buffered — and ``vmem_bytes`` is O(Pmax) again: the gate
accepts GPT-2-small's 64-page table and rejects 100k-token tables,
which ``auto`` serves through the XLA gather. Pages past a slot's
length are still fetched (clipped ids, masked). The same layout costs
a whole-pool layout copy in front of the custom call (XLA keeps the
pool page-minor in HBM; see PERF.md "Bring-up on v5e"). Streaming
pages at O(band) VMEM needs a pool layout whose pages can be DMA'd;
that is a layout change, not a kernel repair.

BANDS: the page walk is computed in ascending PAGE BANDS of
``band_pages`` pages. ``band_pages`` picks the largest divisor of Pmax
whose band working set fits ``BAND_VMEM_BUDGET``, capped at
``MAX_BANDS`` bands (the band loop is Python-unrolled into the trace).
The plan bounds the f32 ``[G, T, C, BW]`` product the compute holds at
once, and — because f32 addition is not associative — it is part of
the numerical contract below: the sizing rule is frozen as PR 20 wrote
it.

EXACTNESS CONTRACT (the reason this kernel looks the way it does): the
serving suite's landing gate is greedy token-identity against the XLA
path, and the repo has twice shipped attention variants that drifted by
~2 bf16 ulps and flipped near-tied greedy argmaxes on real checkpoints
(PR 4/PR 5, see analysis.choreo). A classic flash-style online-softmax
accumulator — running max with ``exp(m_old - m_new)`` rescales folded
into the accumulator — can NEVER be bitwise against the XLA joint
softmax: the rescale multiplies are extra roundings. So the f32 score
row for the FULL context stays resident and normalization is ONE flat
f32 softmax. Concretely the kernel makes two passes over the bands:

  pass 1 (K): each band's scores are per-column sums over C — banding
    is invisible to them bitwise — concatenated with the recent/self
    scores into the one full score row, then the single joint softmax;
  pass 2 (V): each band's PV partial summed over its band width,
    folded in PINNED ASCENDING-BAND ORDER (``banded_fold``). The fold
    order is the ONE place banding touches f32 summation order, so
    the XLA reference path runs the IDENTICAL chunked reduction
    (models.gpt banded PV fold, same ``banded_fold``, same band plan)
    and the interpreted kernel is BITWISE equal to the XLA path on the
    CPU (asserted by tests/test_paged_attn.py down to the f32 pattern)
    across decode + verify, MHA + GQA, ragged lengths, both pool
    precisions, and the greedy/sampled token-identity matrix. The
    accumulation order is machine-checked: analysis.choreo's
    banded-accumulation-order clause extracts the fold's add-tree leaf
    order from the jaxpr and fails if any band lands out of ascending
    order. On the chip the compiled kernel and the XLA program order
    their reductions as their compilers choose; chip_smoke.py holds
    them to a stated tolerance there.

INT8 KV (``scale_k``/``scale_v`` given): the pool payload is int8 with
one f32 power-of-two scale per (page, KV-head) plane
(serving.paged — the KV analogue of quant.py's po2 exactness contract).
Dequantization happens in-kernel, per band: ``f32(q) * scale`` with
``|q| <= 127`` and a po2 scale is EXACT and elementwise, so the band
slice of the dequantized stream equals the dequantized band slice — an
int8 pool behaves like a bf16 pool whose values happen to lie on the
page grid, and the greedy token streams stay invariant across every
engine feature combination (unit-tested at the page level).

Dtype choreography (machine-checked: analysis.choreo extracts the
kernel body's softmax signature and proves it equal to the decode
window's — a bf16-accumulating edit here turns the serving-choreo CI
gate red): bf16 Q/K products formed as f32 upcast-multiplies, f32 score
accumulation, additive mask before the in-softmax scale, one joint f32
exp per layer, f32 probs through the PV sums, output rounded to the
compute dtype once at the end.

Interpret mode is the caller's choice (``interpret=True``, or the
tests' ``pallas_interpret`` fixture): the program never picks it from
a device probe, so a kernel the engine reports as "pallas" is a
compiled one. The XLA gather path stays available as a
config-selected backend (``ServingEngine(paged_kernel="xla")``),
exactly as ops/flash.py keeps naive attention for training.
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# The score-accumulation dtype of both kernels. Module-level so the
# choreography fault-injection test (tests/test_choreo.py) can
# monkeypatch a bf16-accumulating kernel variant and prove the prover
# catches it; the shipped value is load-bearing — f32 accumulation IS
# the decode choreography contract.
SCORE_ACC_DTYPE = jnp.float32

# What the kernels ask the compiler for, and what ``supported`` lets
# the estimate reach. A v5e core has 128 MiB of VMEM; the compiler's
# default scoped limit (16 MiB) is below the page-block residency of a
# 128-page table, so the call raises it. The gap between the two is
# headroom for what the estimate cannot see (Mosaic's own temporaries).
VMEM_LIMIT = 96 * 1024 * 1024
VMEM_BUDGET = 64 * 1024 * 1024

# Band plan sizing, frozen as PR 20 wrote it for its double-buffered
# K/V band stream: the plan fixes the PV fold order of every
# multi-band stream on BOTH the kernel and the XLA reference, so
# moving these constants moves bits, not just memory.
BAND_VMEM_BUDGET = 2 * 1024 * 1024
_PLAN_DEPTH = 2

# The band loop is Python-unrolled into the kernel trace (that is what
# keeps the choreography extractable and the softmax flat), so cap the
# band count: a geometry that would need more bands than this is
# rejected by the gate rather than traced into an enormous program.
MAX_BANDS = 64

# Test hook: force the band plan (pages per band) regardless of the
# VMEM arithmetic, so small-geometry tests can exercise genuinely
# multi-banded kernels. Must divide Pmax. None = auto-size.
_FORCE_BAND_PAGES: tp.Optional[int] = None

# Fault-injection hook for the choreography prover's banded-
# accumulation-order clause: "ascending" is the pinned contract; the
# choreo fault test flips this to "descending" and the prover must
# fail EXACTLY the band-order clause (both the kernel and the XLA
# reference fold through banded_fold, so bitwise kernel==XLA survives
# the flip and no other clause goes red).
_BAND_FOLD_ORDER = "ascending"


def banded_fold(parts: tp.Sequence[Array]) -> Array:
    """Fold the per-band PV partials in the PINNED ascending-band
    order (a left fold: ((o_0 + o_1) + o_2) + ...). f32 addition is
    not associative, so this order IS the bitwise contract between the
    banded kernel and the banded XLA reference — both call exactly
    this function. The recent/self partial is added AFTER the fold,
    outside it (it is not a page band)."""
    seq = list(parts)
    if _BAND_FOLD_ORDER != "ascending":
        seq = seq[::-1]
    out = seq[0]
    for p in seq[1:]:
        out = out + p
    return out


def _band_bytes(band_pages_: int, page_size: int, c: int,
                itemsize: int) -> int:
    """The band plan's sizing rule: K and V band buffers at pool dtype,
    ``_PLAN_DEPTH`` of each, plus — for a sub-f32 pool (bf16, int8) —
    the f32 upcast views of the K and V compute bands."""
    bw = band_pages_ * page_size
    total = 2 * _PLAN_DEPTH * c * bw * itemsize
    if itemsize < 4:
        total += 2 * c * bw * 4
    return total


def band_pages(pmax: int, page_size: int, c: int,
               itemsize: int) -> tp.Optional[int]:
    """Auto-size the page band: the LARGEST divisor of ``pmax`` whose
    band working set fits ``BAND_VMEM_BUDGET``, with at most
    ``MAX_BANDS`` bands (the band loop is unrolled into the trace).
    Returns None when no divisor satisfies both — e.g. a head dim so
    large even one page overflows the band budget, or a
    pathologically-factored Pmax whose only fitting divisors need too
    many bands. The plan depends ONLY on (pmax, page_size, c,
    itemsize): never on head counts, groups, or spec length, so the
    fold order — and with it the f32 bit pattern — is invariant across
    TP degree and spec on/off."""
    if _FORCE_BAND_PAGES is not None:
        assert pmax % _FORCE_BAND_PAGES == 0, (
            f"_FORCE_BAND_PAGES={_FORCE_BAND_PAGES} must divide "
            f"pmax={pmax}"
        )
        return _FORCE_BAND_PAGES
    best = None
    for d in range(1, pmax + 1):
        if pmax % d:
            continue
        if _band_bytes(d, page_size, c, itemsize) <= BAND_VMEM_BUDGET:
            best = d
    if best is None or pmax // best > MAX_BANDS:
        return None
    return best


def resolved_band_pages(pmax: int, page_size: int, c: int,
                        itemsize: int) -> int:
    """The band plan the kernels AND the XLA reference fold actually
    use: the auto-sized (or test-forced) band, falling back to one
    whole-table band when no plan fits — the degenerate case the gate
    keeps off the ``auto`` path but the XLA reference still folds by.
    Shared between ops.paged_attn and models.gpt so the two PV fold
    orders can never diverge."""
    bp = band_pages(pmax, page_size, c, itemsize)
    if bp is None:
        bp = pmax
    assert pmax % bp == 0
    return bp


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(pmax: int, page_size: int, c: int, itemsize: int,
               groups: int = 8, spec_t: int = 1) -> int:
    """Estimated VMEM demand of one (slot, KV head) grid step, in
    bytes. Head count does not enter: the grid runs over KV heads.

    - page blocks: K and V, ``pmax`` each, every ``[C, PS]`` block
      padded to its dtype's (sublane, 128-lane) tile and held twice by
      the pipeline — the O(Pmax) term;
    - one band's compute: the band at pool dtype, its f32 view, and
      the ``[G, T, C, BW]`` f32 product the reductions consume;
    - the full-context f32 score and prob rows ``[G, T, 1, W]`` — the
      flat-softmax residency — whose unit sublane dim pads 8x;
    - an int8 pool's per-position f32 scale rows (K and V), padded and
      double-buffered like any block.

    Checked against the compiler at G4/C128/Pmax128/bf16: this says
    19.3 MiB where Mosaic's scoped allocation was 18.02 MiB."""
    g, t = max(1, groups), max(1, spec_t)
    w = pmax * page_size
    sublanes = 8 * 4 // itemsize
    page = _ceil_to(c, sublanes) * _ceil_to(page_size, 128) * itemsize
    pages = 2 * 2 * pmax * page
    bp = band_pages(pmax, page_size, c, itemsize)
    bw = (pmax if bp is None else bp) * page_size
    band = c * bw * (itemsize + 4) + g * t * c * bw * 4
    scores = 2 * g * t * 8 * w * 4
    total = pages + band + scores
    if itemsize == 1:
        total += 2 * 2 * 8 * w * 4
    return total


def supported(pmax: int, page_size: int, c: int, itemsize: int,
              groups: int = 8, spec_t: int = 1) -> bool:
    """Will the chip's compiler take the kernels at this geometry?
    (``groups`` = query heads per KV head; ``spec_t`` = candidate rows
    per slot in the verify kernel — pass ``speculate + 1`` when
    speculation is on.) Two conditions, both learned from compiling
    for a described v5e: a band plan exists (else the unrolled trace
    is unbounded), and the ``vmem_bytes`` estimate fits
    ``VMEM_BUDGET``. ``ServingEngine(paged_kernel="auto")`` serves
    every geometry this rejects through the XLA gather."""
    if band_pages(pmax, page_size, c, itemsize) is None:
        return False
    return vmem_bytes(
        pmax, page_size, c, itemsize, groups=groups, spec_t=spec_t
    ) <= VMEM_BUDGET


def _band_view(page_refs, sc, b: int, bp: int, ps: int):
    """Band ``b``'s logical [C, BW] f32 stream view: its ``bp`` page
    blocks laid side by side on the lane axis in page order (what the
    XLA path's gathered view holds in columns [b*BW, (b+1)*BW)). For an
    int8 pool the band's columns of the per-position scale row multiply
    the upcast codes — exact (|q| <= 127, po2 scale — quant.py's
    epilogue contract, applied to the KV stream) and elementwise, so
    banding cannot perturb it."""
    pages = [r[...] for r in page_refs[b * bp:(b + 1) * bp]]
    band = pages[0] if bp == 1 else jnp.concatenate(pages, axis=-1)
    if sc is None:
        return band.astype(jnp.float32)
    return band.astype(jnp.float32) * sc[:, b * bp * ps:(b + 1) * bp * ps]


def _decode_kernel(
    # scalar prefetch
    bt_ref,      # [S, Pmax] int32 — consumed by the page index maps
    len_ref,     # [S] int32 — pooled_len
    r_ref,       # [1] int32 — step index within the window
    *refs,
    # q [G, C, 1]; recent K/V rows TRANSPOSED [C, R]; the int8 pool's
    # per-position scale rows [1, W] f32 (K, V) when ``quant``; then
    # Pmax K page blocks and Pmax V page blocks [C, PS]; the output
    # block [G, C, 1] last. Blocks are squeezed to these shapes.
    pmax: int,
    bp: int,
    quant: bool,
):
    del bt_ref
    s = pl.program_id(0)
    q_ref, rkt_ref, rvt_ref = refs[:3]
    refs = refs[3:]
    sc_k = sc_v = None
    if quant:
        sc_k, sc_v = refs[0][...], refs[1][...]  # [1, W] f32
        refs = refs[2:]
    k_pages, v_pages, out_ref = refs[:pmax], refs[pmax:2 * pmax], refs[-1]
    c, ps = k_pages[0].shape
    bw = bp * ps
    nb = pmax // bp
    w = pmax * ps
    rr = rkt_ref.shape[-1]
    # every contraction keeps C on the sublane axis and time on the
    # lane axis, reductions keep their dim: the shapes Mosaic lays out
    # without a layout change (a [G, C] -> [G, C, 1] value reshape is the
    # cast its layout inference refuses)
    qs = q_ref[...]  # [G, C, 1]
    # PASS 1 (K): each band's scores are per-column sums over C, so
    # banding is bitwise-invisible to them; the masked parts
    # concatenate into the ONE full-context f32 score row (the
    # flat-softmax contract — no online rescaling).
    parts = []
    for b in range(nb):
        ck_b = _band_view(k_pages, sc_k, b, bp, ps)  # [C, BW] f32
        # the decode choreography, op for op (decode_paged_at): f32
        # upcast-multiplies, f32 accumulation, mask BEFORE the
        # in-softmax scale
        s_b = jnp.sum(
            qs.astype(SCORE_ACC_DTYPE) * ck_b[None].astype(SCORE_ACC_DTYPE),
            axis=-2, keepdims=True, dtype=SCORE_ACC_DTYPE,
        )  # [G, 1, BW]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bw), 2) + b * bw
        mask_b = jnp.where(idx < len_ref[s], 0.0, -jnp.inf).astype(
            jnp.float32
        )
        parts.append(s_b + mask_b)
    s_rec = jnp.sum(
        qs.astype(SCORE_ACC_DTYPE)
        * rkt_ref[...][None].astype(SCORE_ACC_DTYPE),
        axis=-2, keepdims=True, dtype=SCORE_ACC_DTYPE,
    )  # [G, 1, R]
    ridx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, rr), 2)
    mask_rec = jnp.where(ridx <= r_ref[0], 0.0, -jnp.inf).astype(
        jnp.float32
    )
    s_all = jnp.concatenate(parts + [s_rec + mask_rec], axis=-1)
    probs = jax.nn.softmax(s_all / math.sqrt(c), axis=-1)  # f32, joint
    # PASS 2 (V): each band's PV partial summed over its band width,
    # folded in PINNED ascending-band order — the one place banding
    # touches f32 summation order, matched bitwise by the XLA
    # reference's banded_fold.
    opars = []
    for b in range(nb):
        cv_b = _band_view(v_pages, sc_v, b, bp, ps)  # [C, BW] f32
        p_b = probs[:, :, b * bw:(b + 1) * bw]  # [G, 1, BW] f32
        opars.append(
            jnp.sum(p_b * cv_b[None].astype(jnp.float32), axis=-1,
                    keepdims=True)
        )  # [G, C, 1]
    o_pool = banded_fold(opars)
    p_rec = probs[:, :, w:]  # [G, 1, R]
    o_rec = jnp.sum(
        p_rec * rvt_ref[...][None].astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    out_ref[...] = (o_pool + o_rec).astype(out_ref.dtype)


def _page_specs(layer: int, pmax: int, np_total: int, c: int, ps: int):
    """One BlockSpec per block-table column: grid step (slot i, KV head
    j) receives page ``bt[i, p]`` of this layer and head as a whole
    [C, PS] block, fetched by the Pallas pipeline (which double-buffers
    the next step's pages behind this step's compute). The pool's
    16-wide time-minor pages are smaller than a lane tile, and Mosaic
    refuses every manual DMA slice of such an array ("Slice shape along
    dimension 2 must be aligned to tiling (128), but is 16"); a block
    that spans the array's last two dims whole is the one window it
    accepts. Page ids are clipped like the XLA path's ``mode="clip"``
    gather: pads beyond the slot's live pages fetch a valid page whose
    garbage the -inf mask erases before the softmax."""

    def spec(p):
        def index_map(i, j, bt_ref, *_):
            return (layer, jnp.clip(bt_ref[i, p], 0, np_total - 1), j, 0, 0)

        return pl.BlockSpec((None, None, None, c, ps), index_map)

    return [spec(p) for p in range(pmax)]


def _position_scales(scale: Array, ps: int) -> Array:
    """Gathered per-page scales [S, Pmax, Hkv] -> per-position rows
    [S, Hkv, 1, W]: each page's scale repeated over its PS columns, in
    XLA, where the page-to-lane expansion is free of Mosaic's reshape
    rules (a [BP, PS] -> [BP*PS] merge of sub-tile rows is refused)."""
    sc = jnp.repeat(jnp.transpose(scale, (0, 2, 1)), ps, axis=-1)
    return sc[:, :, None, :]


def _slot_head_spec(shape: tp.Sequence[int]) -> pl.BlockSpec:
    """Grid step (slot i, KV head j) sees ``array[i, j]`` whole, the two
    grid dims squeezed away."""
    rest = tuple(shape[2:])
    return pl.BlockSpec(
        (None, None) + rest, lambda i, j, *_: (i, j) + (0,) * len(rest)
    )


def _paged_call(
    body, scalars, q: Array, rows_t: tp.Sequence[Array], pool_k: Array,
    pool_v: Array, bt: Array, layer: int, scale_k: tp.Optional[Array],
    scale_v: tp.Optional[Array], interpret: bool,
) -> Array:
    """The call both kernels share: grid over (slot, KV head); operands
    are the scalar-prefetched ``scalars`` (block table first), ``q``
    with a unit lane dim, the row buffers pre-transposed to [.., C, R],
    an int8 pool's per-position scale rows, then one block per
    block-table column for K and for V. Returns ``q``-shaped output."""
    s, hkv = q.shape[:2]
    c = q.shape[-1]
    _, np_total, _, _, ps = pool_k.shape
    pmax = bt.shape[1]
    quant = scale_k is not None
    bp = resolved_band_pages(pmax, ps, c, jnp.dtype(pool_k.dtype).itemsize)
    args = [q[..., None], *rows_t]
    if quant:
        args += [_position_scales(scale_k, ps), _position_scales(scale_v, ps)]
    in_specs = [_slot_head_spec(a.shape) for a in args]
    in_specs += 2 * _page_specs(layer, pmax, np_total, c, ps)
    args += pmax * [pool_k] + pmax * [pool_v]
    out_shape = q.shape + (1,)
    # ``interpret`` is only forwarded when asked for: ``interpret=False``
    # spelled out would override the tests' ``pallas_interpret`` fixture,
    # which binds the keyword on ``pl.pallas_call`` itself
    out = pl.pallas_call(
        functools.partial(body, pmax=pmax, bp=bp, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(s, hkv),
            in_specs=in_specs,
            out_specs=_slot_head_spec(out_shape),
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        **({"interpret": True} if interpret else {}),
    )(*scalars, *args)
    return out[..., 0]


def paged_decode_attention(
    q: Array,        # [S, Hkv, G, C] post-rope/norm queries, compute dtype
    pool_k: Array,   # [L, NP, Hkv, C, PS] pool (bf16/f32, or int8)
    pool_v: Array,
    bt: Array,       # [S, Pmax] int32 block tables
    pooled_len: Array,  # [S] int32 — ragged per-slot resident lengths
    rk_l: Array,     # [S, Hkv, R, C] recent K rows, THIS layer
    rv_l: Array,
    r: Array,        # [] int32 — step index within the window
    layer: int,      # STATIC layer index
    scale_k: tp.Optional[Array] = None,  # [S, Pmax, Hkv] f32 gathered
    scale_v: tp.Optional[Array] = None,  # per-page scales (int8 pool)
    interpret: bool = False,
) -> Array:  # [S, Hkv, G, C] compute dtype
    """One decode step's paged attention for all slots: pool part read
    page by page through the block table, recent part from the window's
    write buffer, one joint softmax — bitwise the (banded-fold) XLA
    gather path's result without the gathered HBM intermediate."""
    return _paged_call(
        _decode_kernel, (bt, pooled_len, jnp.reshape(r, (1,))), q,
        (jnp.swapaxes(rk_l, 2, 3), jnp.swapaxes(rv_l, 2, 3)),
        pool_k, pool_v, bt, layer, scale_k, scale_v, interpret,
    )


def _verify_kernel(
    # scalar prefetch
    bt_ref,      # [S, Pmax] int32 — consumed by the page index maps
    start_ref,   # [S] int32 — per-slot write watermark
    *refs,
    # q [G, T, C, 1]; cache-rounded self K/V rows TRANSPOSED [C, T];
    # scale rows [1, W] when ``quant``; Pmax K then Pmax V page blocks
    # [C, PS]; the output block [G, T, C, 1] last.
    pmax: int,
    bp: int,
    quant: bool,
):
    del bt_ref
    s = pl.program_id(0)
    q_ref, kct_ref, vct_ref = refs[:3]
    refs = refs[3:]
    sc_k = sc_v = None
    if quant:
        sc_k, sc_v = refs[0][...], refs[1][...]  # [1, W] f32
        refs = refs[2:]
    k_pages, v_pages, out_ref = refs[:pmax], refs[pmax:2 * pmax], refs[-1]
    c, ps = k_pages[0].shape
    bw = bp * ps
    nb = pmax // bp
    w = pmax * ps
    t = kct_ref.shape[-1]
    qs = q_ref[...]  # [G, T, C, 1]
    # the decode choreography over T candidate rows (verify_paged_at
    # op for op): f32 upcast-multiplies, f32 accumulation, one joint
    # exp, f32 probs through the PV sums — banded exactly like
    # _decode_kernel (pass 1 K scores, flat softmax, pass 2 V fold)
    parts = []
    for b in range(nb):
        ck_b = _band_view(k_pages, sc_k, b, bp, ps)  # [C, BW] f32
        s_b = jnp.sum(
            qs.astype(SCORE_ACC_DTYPE)
            * ck_b[None, None].astype(SCORE_ACC_DTYPE),
            axis=-2, keepdims=True, dtype=SCORE_ACC_DTYPE,
        )  # [G, T, 1, BW]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, bw), 3) + b * bw
        mask_b = jnp.where(idx < start_ref[s], 0.0, -jnp.inf).astype(
            jnp.float32
        )
        parts.append(s_b + mask_b)
    s_self = jnp.sum(
        qs.astype(SCORE_ACC_DTYPE)
        * kct_ref[...][None, None].astype(SCORE_ACC_DTYPE),
        axis=-2, keepdims=True, dtype=SCORE_ACC_DTYPE,
    )  # [G, T, 1, T]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, t, 1, t), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, t, 1, t), 3)
    mask_self = jnp.where(cols <= rows, 0.0, -jnp.inf).astype(jnp.float32)
    s_all = jnp.concatenate(parts + [s_self + mask_self], axis=-1)
    probs = jax.nn.softmax(s_all / math.sqrt(c), axis=-1)  # f32
    opars = []
    for b in range(nb):
        cv_b = _band_view(v_pages, sc_v, b, bp, ps)  # [C, BW] f32
        p_b = probs[:, :, :, b * bw:(b + 1) * bw]  # [G, T, 1, BW] f32
        opars.append(
            jnp.sum(p_b * cv_b[None, None].astype(jnp.float32), axis=-1,
                    keepdims=True)
        )  # [G, T, C, 1]
    o_pool = banded_fold(opars)
    p_self = probs[:, :, :, w:]  # [G, T, 1, T]
    o_self = jnp.sum(
        p_self * vct_ref[...][None, None].astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [G, T, C, 1]
    out_ref[...] = (o_pool + o_self).astype(out_ref.dtype)


def paged_verify_attention(
    q: Array,        # [S, Hkv, G, T, C] compute dtype
    kc: Array,       # [S, Hkv, T, C] cache-rounded self K rows
    vc: Array,
    pool_k: Array,   # [L, NP, Hkv, C, PS]
    pool_v: Array,
    bt: Array,       # [S, Pmax] int32
    start: Array,    # [S] int32 — write watermark (resident tokens)
    layer: int,
    scale_k: tp.Optional[Array] = None,  # [S, Pmax, Hkv] f32 gathered
    scale_v: tp.Optional[Array] = None,
    interpret: bool = False,
) -> Array:  # [S, Hkv, G, T, C]
    """Speculative-verify paged attention: all T candidate rows of every
    slot against its resident pages plus themselves (causal), one joint
    softmax, decode choreography — the kernel twin of
    ``Attention.verify_paged_at`` with the same page-block walk as
    :func:`paged_decode_attention`."""
    return _paged_call(
        _verify_kernel, (bt, start), q,
        (jnp.swapaxes(kc, 2, 3), jnp.swapaxes(vc, 2, 3)),
        pool_k, pool_v, bt, layer, scale_k, scale_v, interpret,
    )
