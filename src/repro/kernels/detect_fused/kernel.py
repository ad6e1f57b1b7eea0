"""Fused detection Pallas kernels: merge -> slope -> median -> top-k.

Two kernels cover the whole detection tail in one launch each:

* ``ns_fused_kernel`` — the non-scalable half.  Grid ``(S, NP)`` with NP
  (row tiles) innermost/sequential: per-scale merge accumulators (count,
  sum, max, p0, inverse-variance sums) live in VMEM scratch and reduce
  across row tiles; when a scale's last tile lands its (4, V) merged
  column is written into the resident M output, and the final grid step
  adds the (optional) device-cached historical columns, derives the
  reference step time from the "max" row, and runs the closed-form
  log-log slope fit + share/deviation flagging — all before leaving the
  kernel.  One launch replaces the merge/stack/slope dispatch chain.
* ``ab_fused_kernel`` — the abnormal half over one (P, V) time matrix
  (live-gathered and zero-padded by ``ops``).  Grid ``(2, NV)``: phase 0
  accumulates per-row step-time partials across column tiles; phase 1
  computes the masked cross-process median per column via bitwise radix
  *selection* (TPU Pallas has no sort primitive — the two middle order
  statistics are found in ``nbits`` counting passes on the order-
  preserving integer keys), flags abnormal entries, and runs a
  tournament top-k (k max/argmin passes per tile, merged across tiles
  through VMEM scratch) that reproduces the reference ranking exactly:
  descending score, ties broken by ascending vid-major flat index.

The pure-jnp merge/slope/flag formulas shared by the fused jnp fast
path (``ops.py``) and the kernel bodies themselves are defined at the
top of this module — single source of truth, so the two paths cannot
drift.

Everything is dtype-generic over f32/f64 (the TPU runs f32, see
``repro.core.detect_jax.precision``); the float<->ordered-integer key
bridge picks int32/int64 to match.  The kernel bodies keep to what the
TPU compiler (Mosaic) lowers: 2-D values, static slices, parameters read
as scalars from SMEM, (1, 1) arrays instead of vector-to-scalar stores,
signed integer reductions only.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.detect import JIT_STRATEGIES, VAR_EPS

_IMAX = JIT_STRATEGIES.index("max")
_ROW_TILE = 1024          # ns kernel: rows per grid step
_COL_TILE = 128           # ab kernel: vertex columns per grid step (lanes)
_STEP_EPS = 1e-12         # step-time clamp, matches the host reference
# ab kernel: whole-fleet (P, 128) column blocks plus the median/top-k
# temporaries outgrow the default 16 MiB scoped VMEM from P = 8192; a
# v5e core has 128 MiB
_AB_VMEM_LIMIT = 100 * 2 ** 20


# -- shared detection math (jnp; used by the fused jnp path and inside
# -- the Pallas kernel bodies) ------------------------------------------

def merge_all_stack(t: jax.Array, var: jax.Array) -> jax.Array:
    """(S, P, V) times + variances -> (4, S, V) merged, rows ordered as
    JIT_STRATEGIES.  Non-positive readings are dead (excluded)."""
    pos = t > 0.0
    cnt = pos.sum(axis=1)                              # (S, V)
    any_pos = cnt > 0
    total = jnp.where(pos, t, 0.0).sum(axis=1)
    mean = jnp.where(any_pos, total / jnp.maximum(cnt, 1), 0.0)
    mx = jnp.where(any_pos, t.max(axis=1), 0.0)
    p0 = t[:, 0, :]
    p0 = jnp.where(p0 > 0.0, p0, mean)
    w = jnp.where(pos, 1.0 / (var + VAR_EPS), 0.0)
    wsum = w.sum(axis=1)
    varm = jnp.where(wsum > 0,
                     (w * t).sum(axis=1) / jnp.where(wsum > 0, wsum, 1.0),
                     0.0)
    return jnp.stack([mean, mx, p0, varm])             # (4, S, V)


def merge_blocks(ts, vs) -> jax.Array:
    """One scale's per-host blocks -> its (4, V) merged column.

    ``ts`` / ``vs`` are tuples of (n_local, V) blocks in global proc
    order.  Every merge is an associative block-level reduction, so the
    stacked matrix never materializes."""
    pos = [t > 0.0 for t in ts]
    cnt = sum(p.sum(axis=0) for p in pos)              # (V,)
    total = sum(jnp.where(p, t, 0.0).sum(axis=0)
                for p, t in zip(pos, ts))
    mx_raw = jnp.stack([t.max(axis=0) for t in ts]).max(axis=0)
    w = [jnp.where(p, 1.0 / (v + VAR_EPS), 0.0)
         for p, v in zip(pos, vs)]
    wsum = sum(wi.sum(axis=0) for wi in w)
    wt = sum((wi * t).sum(axis=0) for wi, t in zip(w, ts))
    any_pos = cnt > 0
    mean = jnp.where(any_pos, total / jnp.maximum(cnt, 1), 0.0)
    mx = jnp.where(any_pos, mx_raw, 0.0)
    p0 = ts[0][0, :]
    p0 = jnp.where(p0 > 0.0, p0, mean)
    varm = jnp.where(wsum > 0,
                     wt / jnp.where(wsum > 0, wsum, 1.0), 0.0)
    return jnp.stack([mean, mx, p0, varm])             # (4, V)


def slope_share_flag(M, logp, present, total_max,
                     ideal_slope, slope_margin, min_share, *,
                     keepdims: bool = False):
    """(..., S, V) merged stack -> (slope, share, flagged), each (..., V).

    ``logp`` holds the S log process counts, as (S,) or (S, 1);
    ``present`` is the (S, V) vertex-exists mask.  Reductions run over
    the scale axis (-2) and the reference scale is a static slice, so
    the same formulas trace inside a TPU Pallas kernel (2-D operands,
    ``keepdims=True`` -> (1, V) results) and in the jnp paths.

    ``share`` is guarded: an all-dead final scale (``total_max <= 0``)
    yields share 0 — and so flags nothing — instead of inf/nan."""
    S = M.shape[-2]
    valid = ((M > 0.0) & present).astype(M.dtype)
    live = valid > 0.0
    x = logp.reshape(-1, 1)                            # (S, 1)
    Y = jnp.where(live, jnp.log(jnp.where(live, M, 1.0)), 0.0)
    n = valid.sum(axis=-2, keepdims=True)
    Sx = (x * valid).sum(axis=-2, keepdims=True)
    Sy = Y.sum(axis=-2, keepdims=True)
    Sxx = (x * x * valid).sum(axis=-2, keepdims=True)
    Sxy = (x * Y).sum(axis=-2, keepdims=True)
    denom = n * Sxx - Sx ** 2
    num = n * Sxy - Sx * Sy
    slope = jnp.where((denom != 0) & (n >= 2),
                      num / jnp.where(denom != 0, denom, 1.0), 0.0)
    last = jax.lax.slice_in_dim(M, S - 1, S, axis=M.ndim - 2)
    share = jnp.where(total_max > 0.0,
                      last / jnp.where(total_max > 0.0, total_max, 1.0),
                      0.0)
    flagged = ((M.sum(axis=-2, keepdims=True) > 0.0)
               & (slope - ideal_slope > slope_margin)
               & (share >= min_share))
    if keepdims:
        return slope, share, flagged
    return slope[..., 0, :], share[..., 0, :], flagged[..., 0, :]


def abnormal_flags(t, typical, abnorm_thd, min_share, step_time):
    """(P, V) times + (V,) or (1, V) typical -> (P, V) abnormal-entry
    mask."""
    active = t.max(axis=0, keepdims=True) > 0.0
    over = ((t > abnorm_thd * typical) & (typical > 0.0)
            & ((t - typical) / step_time >= min_share))
    dead_typical = (typical == 0.0) & (t / step_time >= min_share)
    return (over | dead_typical) & active


# -- float <-> order-preserving integer keys ---------------------------

def key_info(dtype) -> Tuple[jnp.dtype, int]:
    """Signed key dtype + bit width for a float dtype."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.float64):
        return jnp.dtype(jnp.int64), 64
    if jnp.dtype(dtype) == jnp.dtype(jnp.float32):
        return jnp.dtype(jnp.int32), 32
    raise TypeError(f"unsupported detect dtype {dtype}")


def key_floor(dtype) -> jax.Array:
    """The smallest key: strictly below every float's key, -inf included
    (top-k extraction parks taken entries there)."""
    k, _ = key_info(dtype)
    return jnp.array(jnp.iinfo(k).min, k)


def _flip(b: jax.Array, bits: int) -> jax.Array:
    # negative floats: flip the magnitude bits, so larger magnitudes
    # order lower; an involution, so it also maps keys back to bits
    return b ^ ((b >> (bits - 1)) & jnp.array(jnp.iinfo(b.dtype).max,
                                             b.dtype))


def to_key(x: jax.Array) -> jax.Array:
    """Bitcast floats to signed integer keys whose order matches the
    float total order (-inf < ... < -0 < +0 < ... < +inf).

    Integer keys are the whole trick: XLA's single-operand integer sort
    is ~13x faster than a float sort on CPU, and the Pallas median runs
    bitwise radix selection, which needs integer keys anyway.  Signed,
    because Mosaic has no reductions over unsigned integers."""
    k, bits = key_info(x.dtype)
    return _flip(jax.lax.bitcast_convert_type(x, k), bits)


def from_key(key: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`to_key`."""
    _, bits = key_info(dtype)
    return jax.lax.bitcast_convert_type(_flip(key, bits), jnp.dtype(dtype))


# -- non-scalable kernel ------------------------------------------------

def _ns_kernel(t_ref, var_ref, hist_ref, logp_ref, present_ref, top_ref,
               par_ref, m_out, slope_out, share_out, flag_out,
               cnt, total, mx, wsum, wt, p0,
               *, n_data: int, n_hist: int):
    s = pl.program_id(0)
    p = pl.program_id(1)
    last_p = pl.num_programs(1) - 1
    n_all = n_hist + n_data
    t = t_ref[0]                                       # (TP, V)
    v = var_ref[0]

    @pl.when(p == 0)
    def _init_scale():
        cnt[...] = jnp.zeros_like(cnt)
        total[...] = jnp.zeros_like(total)
        mx[...] = jnp.full_like(mx, -jnp.inf)
        wsum[...] = jnp.zeros_like(wsum)
        wt[...] = jnp.zeros_like(wt)
        p0[...] = t[0:1, :]                            # true row 0: pad
                                                       # rows are appended
    pos = t > 0.0
    cnt[...] += pos.astype(t.dtype).sum(axis=0, keepdims=True)
    total[...] += jnp.where(pos, t, 0.0).sum(axis=0, keepdims=True)
    mx[...] = jnp.maximum(mx[...], t.max(axis=0, keepdims=True))
    w = jnp.where(pos, 1.0 / (v + VAR_EPS), 0.0)
    wsum[...] += w.sum(axis=0, keepdims=True)
    wt[...] += (w * t).sum(axis=0, keepdims=True)

    # Scale s's merged column lands in row n_hist + s of the resident M
    # output.  Mosaic has no dynamic sublane store, so every data scale
    # gets its own predicated branch with a static row.
    for si in range(n_data):
        @pl.when((s == si) & (p == last_p))
        def _scale_column(row=n_hist + si):
            any_pos = cnt[...] > 0
            mean = jnp.where(any_pos,
                             total[...] / jnp.maximum(cnt[...], 1.0), 0.0)
            p0v = p0[...]
            merged = (mean, jnp.where(any_pos, mx[...], 0.0),
                      jnp.where(p0v > 0.0, p0v, mean),
                      jnp.where(wsum[...] > 0,
                                wt[...] / jnp.where(wsum[...] > 0,
                                                    wsum[...], 1.0), 0.0))
            for r, col in enumerate(merged):           # JIT_STRATEGIES rows
                m_out[r, row:row + 1, :] = col

    @pl.when((s == n_data - 1) & (p == last_p))
    def _tail():
        for r in range(4):
            if n_hist:
                m_out[r, 0:n_hist, :] = hist_ref[r]
        ref_max = m_out[_IMAX, n_all - 1:n_all, :]     # (1, V)
        internal = (ref_max * top_ref[...]).sum(axis=1, keepdims=True)
        total_max = jnp.where(par_ref[0, 4] > 0.0, par_ref[0, 3], internal)
        logp = logp_ref[...]
        present = present_ref[...] > 0.0
        for r in range(4):
            slope, share, flagged = slope_share_flag(
                m_out[r], logp, present, total_max, par_ref[0, 0],
                par_ref[0, 1], par_ref[0, 2], keepdims=True)
            slope_out[r:r + 1, :] = slope
            share_out[r:r + 1, :] = share
            flag_out[r:r + 1, :] = flagged.astype(slope.dtype)


@functools.partial(jax.jit, static_argnames=("n_hist", "interpret"))
def ns_fused_kernel(t: jax.Array, var: jax.Array, hist: jax.Array,
                    logp: jax.Array, present: jax.Array,
                    top_mask: jax.Array, params: jax.Array,
                    *, n_hist: int, interpret: bool = False):
    """One-launch non-scalable detection.

    t, var: (S_d, P, V) data scales (P padded to a row-tile multiple
    with zero = dead rows).  hist: (4, H, V) device-cached merged columns
    of completed scales, prepended to the freshly merged data scales
    (pass a (4, 1, V) dummy with n_hist=0 when uncached).  logp: (S, 1)
    log process counts over ALL S = n_hist + S_d scales; present: (S, V)
    0/1; top_mask: (1, V) 0/1 root-children columns; params: (1, 8)
    [ideal_slope, slope_margin, min_share, total_max, use_total, 0, 0,
    0], read as scalars from SMEM.  Returns (M (4, S, V), slope, share,
    flagged-as-float (4, V))."""
    S_d, P, V = t.shape
    TP = P if P <= _ROW_TILE else _ROW_TILE
    assert P % TP == 0, (P, TP)
    NP = P // TP
    S_t = n_hist + S_d
    dt = t.dtype
    kernel = functools.partial(_ns_kernel, n_data=S_d, n_hist=n_hist)
    return pl.pallas_call(
        kernel,
        grid=(S_d, NP),
        in_specs=[
            pl.BlockSpec((1, TP, V), lambda s, p: (s, p, 0)),
            pl.BlockSpec((1, TP, V), lambda s, p: (s, p, 0)),
            pl.BlockSpec((4, max(n_hist, 1), V), lambda s, p: (0, 0, 0)),
            pl.BlockSpec((S_t, 1), lambda s, p: (0, 0)),
            pl.BlockSpec((S_t, V), lambda s, p: (0, 0)),
            pl.BlockSpec((1, V), lambda s, p: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((4, S_t, V), lambda s, p: (0, 0, 0)),
            pl.BlockSpec((4, V), lambda s, p: (0, 0)),
            pl.BlockSpec((4, V), lambda s, p: (0, 0)),
            pl.BlockSpec((4, V), lambda s, p: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((4, S_t, V), dt),
            jax.ShapeDtypeStruct((4, V), dt),
            jax.ShapeDtypeStruct((4, V), dt),
            jax.ShapeDtypeStruct((4, V), dt),
        ],
        scratch_shapes=[pltpu.VMEM((1, V), dt) for _ in range(6)],
        interpret=interpret,
        name="detect_non_scalable",
    )(t, var, hist, logp, present, top_mask, params)


# -- abnormal kernel ----------------------------------------------------

def _max2(x: jax.Array) -> jax.Array:
    """Max of a 2-D array as a (1, 1) array (sublanes, then lanes)."""
    return x.max(axis=0, keepdims=True).max(axis=1, keepdims=True)


def _min2(x: jax.Array) -> jax.Array:
    return x.min(axis=0, keepdims=True).min(axis=1, keepdims=True)


def _select_rank(keys: jax.Array, rank: jax.Array, nbits: int,
                 count_dtype) -> jax.Array:
    """Per-column rank-``rank`` order statistic of integer keys.

    MSB-first radix selection: ``eq`` marks (0/1) the rows still
    matching the decided high bits; each pass counts how many of those
    have the current bit clear and descends left or right.  ``nbits``
    counting passes over the (P, TV) tile — no sort primitive needed,
    which is what lets the median run inside a TPU Pallas kernel at
    all.  The keys are signed, so the sign bit is read inverted (set =
    lower) and flipped back into the result.  ``rank`` is (1, 1);
    counts and ranks are exact integers in ``count_dtype`` (a float: P
    stays far below 2**24)."""
    u = keys.dtype
    one = jnp.array(1, u)
    zero = jnp.array(0, u)
    sign = jnp.array(jnp.iinfo(u).min, u)
    prefix = jnp.zeros((1, keys.shape[1]), u)
    rr = jnp.broadcast_to(rank, (1, keys.shape[1]))
    eq = jnp.ones(keys.shape, count_dtype)

    def body(i, st):
        prefix, rr, eq = st
        bit = (nbits - 1 - i).astype(u)
        kb = (((keys >> bit) & one) != zero) != (i == 0)   # (P, TV)
        cnt0 = jnp.where(kb, 0.0, eq).sum(axis=0, keepdims=True)
        go = rr >= cnt0                                # (1, TV)
        prefix = jnp.where(go, prefix | (one << bit), prefix)
        rr = jnp.where(go, rr - cnt0, rr)
        eq = jnp.where(kb == go, eq, 0.0)
        return prefix, rr, eq

    prefix, _, _ = jax.lax.fori_loop(0, nbits, body, (prefix, rr, eq))
    return prefix ^ sign                               # (1, TV)


def _extract_topk(skeys, sidx, seed_keys, seed_idx, k: int):
    """k rounds of (max key, min index among maxes) extraction over the
    (P, TV) tile and the (1, k) running cross-tile best; extracted
    entries drop to the key floor (strictly below every real score key,
    -inf included).  Everything stays 2-D: the winners are written into
    (1, k) rows by lane mask, not by dynamic index."""
    u = skeys.dtype
    floor = jnp.array(jnp.iinfo(u).min, u)
    imax = jnp.iinfo(jnp.int32).max
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(i, st):
        sk, kk, ok, oi = st
        m = jnp.maximum(_max2(sk), _max2(kk))          # (1, 1)
        pick = jnp.minimum(_min2(jnp.where(sk == m, sidx, imax)),
                           _min2(jnp.where(kk == m, seed_idx, imax)))
        sk = jnp.where((sk == m) & (sidx == pick), floor, sk)
        kk = jnp.where((kk == m) & (seed_idx == pick), floor, kk)
        ok = jnp.where(lane == i, m, ok)
        oi = jnp.where(lane == i, pick, oi)
        return sk, kk, ok, oi

    ok = jnp.full((1, k), floor)
    oi = jnp.full((1, k), imax, jnp.int32)
    _, _, ok, oi = jax.lax.fori_loop(
        0, k, body, (skeys, seed_keys, ok, oi))
    return ok, oi


def _ab_kernel(t_ref, valid_ref, top_ref, par_ref,
               order_out, score_out, count_out, typ_out,
               step_scr, best_k, best_i, cnt_scr,
               *, k: int, nv: int, tv: int, nbits: int):
    ph = pl.program_id(0)
    cv = pl.program_id(1)
    t = t_ref[...]                                     # (P, TV)
    validf = valid_ref[...]                            # (P, 1)
    vb = validf > 0.0
    dt = t.dtype
    u, _ = key_info(dt)

    @pl.when((ph == 0) & (cv == 0))
    def _init_step():
        step_scr[...] = jnp.zeros_like(step_scr)

    @pl.when(ph == 0)
    def _accum_step():
        step_scr[...] += (t * top_ref[...]).sum(axis=1, keepdims=True)

    @pl.when(ph == 1)
    def _detect():
        sv = _max2(jnp.where(vb, step_scr[...], 0.0))  # (1, 1)
        sv = jnp.where(sv > 0.0, sv, jnp.array(_STEP_EPS, dt))
        step = jnp.where(par_ref[0, 3] > 0.0, par_ref[0, 2], sv)
        abnorm_thd, min_share = par_ref[0, 0], par_ref[0, 1]
        n_live = jnp.maximum(validf.sum(axis=0, keepdims=True), 1.0)
        keys = jnp.where(vb, to_key(t), to_key(jnp.full_like(t, jnp.inf)))
        lo = from_key(_select_rank(keys, jnp.floor((n_live - 1.0) * 0.5),
                                   nbits, dt), dt)
        hi = from_key(_select_rank(keys, jnp.floor(n_live * 0.5),
                                   nbits, dt), dt)
        typical = 0.5 * (lo + hi)                      # (1, TV)
        typ_out[...] = typical
        tm = jnp.where(vb, t, 0.0)
        flags = abnormal_flags(tm, typical, abnorm_thd, min_share,
                               step) & vb
        add = flags.astype(jnp.int32).sum(axis=0, keepdims=True).sum(
            axis=1, keepdims=True)                     # (1, 1)
        cnt_scr[...] = jnp.where(cv == 0, add, cnt_scr[...] + add)

        neg = to_key(jnp.full_like(t, -jnp.inf))
        skeys = jnp.where(flags, to_key(tm - typical), neg)
        P = t.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, skeys.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, skeys.shape, 1)
        lidx = (cv * tv + cols) * P + rows             # global vid-major

        imax = jnp.iinfo(jnp.int32).max
        seed_k = jnp.where(cv == 0, jnp.full((1, k), key_floor(dt)),
                           best_k[...])
        seed_i = jnp.where(cv == 0, jnp.full((1, k), imax, jnp.int32),
                           best_i[...])
        ok, oi = _extract_topk(skeys, lidx, seed_k, seed_i, k)
        best_k[...] = ok
        best_i[...] = oi

        @pl.when(cv == nv - 1)
        def _emit():
            order_out[...] = best_i[...]
            score_out[...] = from_key(best_k[...], dt)
            count_out[...] = cnt_scr[...]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ab_fused_kernel(t: jax.Array, valid: jax.Array, top_mask: jax.Array,
                    params: jax.Array, *, k: int, interpret: bool = False):
    """One-launch abnormal detection over a (P, V) time matrix.

    valid: (P, 1) 0/1 live-row mask (degraded fleets; all-ones
    otherwise).  top_mask: (1, V) 0/1 step-time columns.  params: (1, 8)
    [abnorm_thd, min_share, step_time, use_step, 0...], read as scalars
    from SMEM.  V must be a lane-tile multiple (ops pads with zero
    columns — dead, never flagged, and their -inf scores rank after
    every real entry).  Returns (order (1, k) int32 flat vid-major,
    scores (1, k), count (1, 1) int32, typical (1, V)); entries past the
    flagged count are the reference's -inf tail, exactly as the stable
    argsort yields.

    The whole fleet's rows sit in one VMEM block per column tile, next
    to several (P, 128) temporaries of the median and the top-k, so the
    scoped VMEM limit is raised to fit them; a row-tiled median is
    future work."""
    P, V = t.shape
    tv = V if V <= _COL_TILE else _COL_TILE
    assert V % tv == 0, (V, tv)
    nv = V // tv
    dt = t.dtype
    u, nbits = key_info(dt)
    kernel = functools.partial(_ab_kernel, k=k, nv=nv, tv=tv, nbits=nbits)
    return pl.pallas_call(
        kernel,
        grid=(2, nv),
        in_specs=[
            pl.BlockSpec((P, tv), lambda ph, cv: (0, cv)),
            pl.BlockSpec((P, 1), lambda ph, cv: (0, 0)),
            pl.BlockSpec((1, tv), lambda ph, cv: (0, cv)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda ph, cv: (0, 0)),
            pl.BlockSpec((1, k), lambda ph, cv: (0, 0)),
            pl.BlockSpec((1, 1), lambda ph, cv: (0, 0)),
            pl.BlockSpec((1, tv), lambda ph, cv: (0, cv)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, k), dt),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, V), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((P, 1), dt),
            pltpu.VMEM((1, k), u),
            pltpu.VMEM((1, k), jnp.int32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_AB_VMEM_LIMIT),
        interpret=interpret,
        name="detect_abnormal",
    )(t, valid, top_mask, params)
