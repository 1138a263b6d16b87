"""Pallas TPU kernel: streaming fused refine — masked ED + top-k in one pass.

The refine stage (paper §VI) ranks every record of the planner-selected
(partition, trie-node) targets by exact ED and keeps the k best.  The dense
path gathers ``store.data[sel_part]`` into a ``[Q, MP, cap, n]`` tensor,
materialises the ``[Q, MP, cap]`` distance tensor, and runs a separate
top-k — fine on CPU, a memory wall on device once Q and the slot budget
grow (the gather alone is Q×MP×cap×n×4 bytes of HBM traffic and residency).

This kernel streams instead.  Grid = (Q, MP, cap/BLOCK_C); each step DMAs
one ``[BLOCK_C, n]`` candidate block of one query's plan entry straight out
of the partition store in HBM — the entry's partition id is read from the
scalar-prefetched plan (``PrefetchScalarGridSpec``), so there is no
host-side gather at all — and then, entirely in VMEM/registers:

  * computes the block's squared EDs (‖q‖² − 2·q·xᵀ + ‖x‖², MXU matmul at
    full f32 precision — the subtraction cancels, so a single bf16 pass
    would break exactness);
  * applies the DFS-tag interval mask of the targeting trie node and the
    segment-dedupe predicate (a record already covered by an earlier
    same-partition plan entry is dropped — plan entries arrive sorted by
    partition id, exactly like the dense path's segmented scan) inline;
  * folds the block into a running per-query k-best accumulator held in
    the revisited output block: while the block's best candidate beats the
    accumulator's worst entry, it replaces that entry (a data-dependent
    loop, so a block that cannot improve the answer costs one compare).

Every operand carries a leading singleton axis so the last two block dims
equal the array dims (the TPU lowering's tiling rule), and the accumulator
is ``kp = round_up(k, 128)`` lanes wide with the lanes past ``k`` never
selected — so any k, including the paper's K=500, lowers.

Nothing of shape ``[Q, MP, cap]`` (let alone the gathered rows) ever
exists: the working set per grid step is the BLOCK_C×n candidate tile plus
a few BLOCK_C- and kp-wide rows, ≲ BLOCK_C·n·4 bytes ≈ 0.5 MB at the
defaults — comfortably inside VMEM with double-buffering headroom.

Exactness: per-candidate distances are independent dot products, so
blocking does not change them.  The accumulator keeps the k smallest
(distance, flat index) pairs, with the flat index ``entry·cap + slot`` of
the partition-sorted plan; the wrapper sorts them by that key, which
reproduces ``jax.lax.top_k`` over the full flat candidate axis (lowest flat
index first among equal distances) — gids match the dense oracle exactly
under the tie-break rule, distances to fp rounding of the dot.  Slots with
fewer than k candidates keep the +inf/-1 initialisation, which the wrapper
maps to the ``PAD_DIST``/gid=-1 convention — identical to the dense path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 512
LANES = 128
_INF = 3.4e38  # python float: jnp scalars would be captured as consts
_FLAT_PAD = 2 ** 31 - 1  # flat index of an empty accumulator slot (minus lane)


def pick_block_c(cap: int) -> int:
    """Trace-time candidate-block size for a store of slot capacity ``cap``.

    A store of at most ``DEFAULT_BLOCK_C`` slots is one block of exactly
    ``cap`` (a block dim equal to the array dim always lowers, and small
    delta stores stop streaming mostly-masked padding); larger stores
    stream ``DEFAULT_BLOCK_C``-wide blocks, a multiple of the 128-lane
    tile, with the ragged tail masked by index.  Callers pin ``block_c``
    explicitly to override.
    """
    return max(1, min(int(cap), DEFAULT_BLOCK_C))


def _refine_topk_kernel(sel_ref, q_ref, data_ref, norms_ref, dfs_ref,
                        gid_ref, sp_ref, lo_ref, hi_ref,
                        outd_ref, outg_ref, outf_ref,
                        *, k: int, block_c: int, cap: int, mp: int):
    """One candidate block of one (query, plan-entry) pair.

    ``sel_ref`` is the scalar-prefetched ``[Q, MP]`` partition-id plan (it
    already steered this step's DMA via the index maps); ``sp/lo/hi_ref``
    are the same plan rows in VMEM for the inline mask + dedupe.  The
    outputs are the unsorted accumulator: distance, gid and flat index.
    """
    s = pl.program_id(1)
    c = pl.program_id(2)
    kp = outd_ref.shape[-1]
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    live_k = lane_k < k

    @pl.when((s == 0) & (c == 0))
    def _init():
        outd_ref[0] = jnp.full((1, kp), _INF, jnp.float32)
        outg_ref[0] = jnp.full((1, kp), -1, jnp.int32)
        outf_ref[0] = _FLAT_PAD - lane_k      # distinct, above any real one

    qv = q_ref[0].astype(jnp.float32)                         # [1, n]
    rows = data_ref[0].astype(jnp.float32)                    # [bc, n]
    q2 = jnp.sum(qv * qv)
    dots = jax.lax.dot_general(qv, rows, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    d2 = jnp.maximum(q2 - 2.0 * dots + norms_ref[0], 0.0)     # [1, bc]

    dfs = dfs_ref[0]                                          # [1, bc]
    gid = gid_ref[0]
    parts, los, his = sp_ref[0], lo_ref[0], hi_ref[0]         # [1, mp]

    # this entry's (partition, interval): one-hot extract at slot s (masked
    # sum instead of a dynamic VMEM index — Mosaic-safe, mp is small)
    iota_mp = jax.lax.broadcasted_iota(jnp.int32, (1, mp), 1)
    onehot = iota_mp == s
    part_s = jnp.sum(jnp.where(onehot, parts, 0))
    lo_s = jnp.sum(jnp.where(onehot, los, 0))
    hi_s = jnp.sum(jnp.where(onehot, his, 0))

    # interval mask; the cap-tail of a ragged last block is masked by index
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
    cidx = c * block_c + lane_c
    incl = (gid >= 0) & (dfs >= lo_s) & (dfs < hi_s) & (part_s >= 0) \
        & (cidx < cap)

    # segment dedupe: drop records an earlier same-partition entry covered
    earlier = (iota_mp < s) & (parts == part_s)               # [1, mp]
    dcol = dfs[0][:, None]                                    # [bc, 1]
    covered = jnp.any(earlier & (dcol >= los) & (dcol < his),
                      axis=1)[None, :]                        # [1, bc]
    incl = incl & ~covered

    cand_d = jnp.where(incl, d2, _INF)
    flat = s * cap + cidx

    def worst(acc_d, acc_f):
        # the entry top_k would drop first: largest distance, then the
        # largest flat index (flat indices are distinct, so it is unique)
        wd = jnp.max(jnp.where(live_k, acc_d, -1.0))
        wf = jnp.max(jnp.where(live_k & (acc_d == wd), acc_f, -1))
        return wd, wf

    # Every candidate of this block has a larger flat index than anything
    # the accumulator holds from earlier steps, and candidates leave the
    # block in (distance, flat) order — so "beats the worst entry" is a
    # strict distance compare.
    def cond(carry):
        m, wd = carry[0], carry[5]
        return m < wd

    def body(carry):
        m, cd, acc_d, acc_g, acc_f, _, wf = carry
        pos = jnp.min(jnp.where(cd == m, lane_c, block_c))
        hit = lane_c == pos
        g_new = jnp.sum(jnp.where(hit, gid, 0))
        f_new = jnp.sum(jnp.where(hit, flat, 0))
        slot = live_k & (acc_f == wf)
        acc_d = jnp.where(slot, m, acc_d)
        acc_g = jnp.where(slot, g_new, acc_g)
        acc_f = jnp.where(slot, f_new, acc_f)
        cd = jnp.where(hit, _INF, cd)
        wd, wf = worst(acc_d, acc_f)
        return jnp.min(cd), cd, acc_d, acc_g, acc_f, wd, wf

    acc_d, acc_g, acc_f = outd_ref[0], outg_ref[0], outf_ref[0]
    wd, wf = worst(acc_d, acc_f)
    _, _, acc_d, acc_g, acc_f, _, _ = jax.lax.while_loop(
        cond, body, (jnp.min(cand_d), cand_d, acc_d, acc_g, acc_f, wd, wf))
    outd_ref[0] = acc_d
    outg_ref[0] = acc_g
    outf_ref[0] = acc_f


@functools.partial(jax.jit, static_argnames=("k", "block_c", "interpret"))
def refine_topk(data: jnp.ndarray, norms: jnp.ndarray, rec_dfs: jnp.ndarray,
                rec_gid: jnp.ndarray, queries: jnp.ndarray,
                sel_part: jnp.ndarray, sel_lo: jnp.ndarray,
                sel_hi: jnp.ndarray, k: int, *,
                block_c: Optional[int] = None,
                interpret: bool = False):
    """Streaming fused masked-ED + top-k over the partition store.

    Args:
      data / norms / rec_dfs / rec_gid: the partition store columns,
        ``[P, cap, n]`` / ``[P, cap]`` ×3.
      queries: ``[Q, n]``.
      sel_part / sel_lo / sel_hi: ``[Q, MP]`` plan, **sorted by partition
        id along the entry axis** (pads first — the dedupe predicate needs
        same-partition entries contiguous, as in the dense path).
      k: answers per query.
      block_c: candidate-block width; None (default) picks it at trace
        time from the store capacity via :func:`pick_block_c`.  Any value
        is numerically equivalent — blocking never changes the per-record
        distances or the merge order.

    Returns:
      (d2, gid): ``[Q, k]`` ascending **squared** ED (+inf beyond the
      candidate pool) and record ids (−1 there) — callers apply sqrt and
      the sentinel convention.
    """
    qn, n = queries.shape
    mp = sel_part.shape[1]
    p, cap = data.shape[0], data.shape[1]
    if qn == 0 or mp == 0:
        return (jnp.full((qn, k), _INF, jnp.float32),
                jnp.full((qn, k), -1, jnp.int32))
    bc = pick_block_c(cap) if block_c is None \
        else min(block_c, max(cap, 1))
    nblocks = pl.cdiv(cap, bc)
    kp = pl.cdiv(k, LANES) * LANES

    # leading singleton axes: the last two block dims equal the array dims
    row3 = lambda x: x.reshape(x.shape[0], 1, x.shape[1])
    plan_row = lambda q, s, c, sel: (q, 0, 0)
    store_row = lambda q, s, c, sel: (jnp.maximum(sel[q, s], 0), 0, c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qn, mp, nblocks),
        in_specs=[
            pl.BlockSpec((1, 1, n), plan_row),
            pl.BlockSpec((1, bc, n),
                         lambda q, s, c, sel: (jnp.maximum(sel[q, s], 0),
                                               c, 0)),
            pl.BlockSpec((1, 1, bc), store_row),
            pl.BlockSpec((1, 1, bc), store_row),
            pl.BlockSpec((1, 1, bc), store_row),
            pl.BlockSpec((1, 1, mp), plan_row),
            pl.BlockSpec((1, 1, mp), plan_row),
            pl.BlockSpec((1, 1, mp), plan_row),
        ],
        out_specs=[pl.BlockSpec((1, 1, kp), plan_row)] * 3,
    )
    acc_d, acc_g, acc_f = pl.pallas_call(
        functools.partial(_refine_topk_kernel, k=k, block_c=bc, cap=cap,
                          mp=mp),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((qn, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((qn, 1, kp), jnp.int32),
                   jax.ShapeDtypeStruct((qn, 1, kp), jnp.int32)],
        name="climber_refine_topk",
        interpret=interpret,
    )(sel_part, row3(queries), data, norms.reshape(p, 1, cap),
      rec_dfs.reshape(p, 1, cap), rec_gid.reshape(p, 1, cap),
      row3(sel_part), row3(sel_lo), row3(sel_hi))
    # the accumulator is an unordered k-best set: order it by (distance,
    # flat index) — jax.lax.top_k's order over the flat candidate axis
    d2, _, gid = jax.lax.sort(
        (acc_d[:, 0, :k], acc_f[:, 0, :k], acc_g[:, 0, :k]), num_keys=2)
    return d2, gid
