"""Fused one-launch detection kernels + historical-scale device cache.

Pins the fused ops (``repro.kernels.detect_fused``) three ways:

* PARITY — fused-jnp and Pallas-interpret modes against the pure-numpy
  oracle (``ref.py``): flags, winner order and counts EXACT, floats to
  1e-12 in f64 (XLA reassociates sums) and 1e-4 under
  ``SCALANA_DETECT_F32``; the view entry points are pinned against the
  numpy detectors in ``repro.core.detect``.
* EDGE CASES — empty flag sets, an all-dead scale, degraded fleets
  through the padded live-mask kernel, jit-cache stability across
  live-set sizes (a flapping host must not retrace).
* CACHE — historical scales' merged columns stay device-resident across
  detect calls: a steady-state detect with one dirty live scale uploads
  ONLY the dirty rows and launches <= 2 fused kernels (asserted via
  ``launch_counts``, not inferred from timings); writes, dtype flips
  and layout changes invalidate exactly the affected columns.

Everything here needs jax; the module skips cleanly without it.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp

from repro.core import detect_abnormal, detect_non_scalable, detect_jax
from repro.core.inject import simulate
from repro.kernels.detect_fused import ops, ref

from tests.test_device_detect import _ab_key, _step_psg


def enable_x64():
    """The float64 detection context (the CPU parity precision)."""
    return detect_jax.precision(np.float64)[1]


MODES = [(None, "jnp"), (True, "interpret")]
ARGS = dict(ideal_slope=0.0, slope_margin=0.05, min_share=0.01)


def _case(seed=0, S=3, P=37, V=11, dtype=np.float64):
    """Random stacked detection inputs with dead readings and absent
    vertices — the shapes deliberately off the tile sizes."""
    rng = np.random.default_rng(seed)
    t = rng.uniform(0, 2, (S, P, V))
    t[t < 0.3] = 0.0
    var = rng.uniform(0, 0.1, (S, P, V))
    present = rng.random((S, V)) > 0.1
    scales = [P // 4, P // 2, P][-S:]
    top = np.array([2, 7, 3], np.int32) % V
    return (t.astype(dtype), var.astype(dtype), present, scales, top)


# ---------------------------------------------------------------------------
# parity: fused (jnp + interpret) == numpy oracle, f64
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret,tag", MODES)
def test_non_scalable_stacked_matches_oracle(interpret, tag):
    t, var, present, scales, top = _case()
    tmax = float(t[-1][:, top].max(axis=0, initial=0.0).sum())
    for total in (tmax, None):                     # external + in-kernel
        Mr, slr, shr, flr = ref.non_scalable_ref(
            scales, t, var, present, total_max=total,
            top=None if total is not None else top, **ARGS)
        with enable_x64():
            M, sl, sh, fl = ops.fused_non_scalable(
                jnp.asarray(t), jnp.asarray(var),
                jnp.asarray(np.log(np.asarray(scales, np.float64))),
                jnp.asarray(present), total_max=total,
                top_idx=jnp.asarray(top), interpret=interpret, **ARGS)
        np.testing.assert_allclose(np.asarray(M), Mr, rtol=0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(sl), slr, rtol=0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(sh), shr, rtol=0, atol=1e-12)
        np.testing.assert_array_equal(np.asarray(fl), flr)


@pytest.mark.parametrize("interpret,tag", MODES)
def test_non_scalable_live_blocks_plus_hist_matches_oracle(interpret, tag):
    """Steady-state shape: live scale as device blocks + historical
    merged columns spliced in — same answer as the full stacked merge."""
    t, var, present, scales, top = _case(seed=1)
    Mr, slr, shr, flr = ref.non_scalable_ref(scales, t, var, present,
                                             top=top, **ARGS)
    hist = ref.merge_all_ref(t[:-1], var[:-1])     # (4, S-1, V)
    cuts = [t.shape[1] // 3, 2 * t.shape[1] // 3]
    with enable_x64():
        M, sl, sh, fl = ops.fused_non_scalable_live(
            [jnp.asarray(b) for b in np.split(t[-1], cuts, axis=0)],
            [jnp.asarray(b) for b in np.split(var[-1], cuts, axis=0)],
            jnp.asarray(hist),
            jnp.asarray(np.log(np.asarray(scales, np.float64))),
            jnp.asarray(present), jnp.asarray(top),
            interpret=interpret, **ARGS)
    np.testing.assert_allclose(np.asarray(M), Mr, rtol=0, atol=1e-12)
    np.testing.assert_allclose(np.asarray(sl), slr, rtol=0, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(fl), flr)


@pytest.mark.parametrize("interpret,tag", MODES)
def test_abnormal_matches_oracle_exactly(interpret, tag):
    """Winners, scores, count and typical all EXACT: the integer-key
    median reads the same order statistics as numpy, and the tournament
    reproduces the stable vid-major ranking including the -inf tail."""
    t, var, present, scales, top = _case(seed=2)
    k = 9
    cuts = [10, 20]
    orr, svr, cr, tyr = ref.abnormal_ref(t[-1], top, 1.5, 0.001, k)
    with enable_x64():
        blocks = [jnp.asarray(b) for b in np.split(t[-1], cuts, axis=0)]
        o, sv, c, ty = ops.fused_abnormal(blocks, jnp.asarray(top),
                                          1.5, 0.001, k,
                                          interpret=interpret)
    np.testing.assert_array_equal(np.asarray(o), orr)
    np.testing.assert_array_equal(np.asarray(sv), svr)
    assert int(c) == cr
    np.testing.assert_array_equal(np.asarray(ty), tyr)

    # external step time (the host-fed entry point's shape)
    orr2, _, cr2, _ = ref.abnormal_ref(t[-1], top, 1.5, 0.001, k,
                                       step_time=3.25)
    with enable_x64():
        o2, _, c2, _ = ops.fused_abnormal([jnp.asarray(t[-1])], None,
                                          1.5, 0.001, k, step_time=3.25,
                                          interpret=interpret)
    np.testing.assert_array_equal(np.asarray(o2), orr2)
    assert int(c2) == cr2


@pytest.mark.parametrize("interpret,tag", MODES)
def test_abnormal_live_masked_degraded_fleet(interpret, tag):
    """The padded live-gather variant: dead rows excluded from median,
    step time, flags and ranking — numpy row-subset semantics."""
    t, var, present, scales, top = _case(seed=4)
    P, k = t.shape[1], 7
    rng = np.random.default_rng(5)
    live = np.sort(rng.choice(P, size=P - 9, replace=False))
    lpad = np.zeros(P, np.int32)
    lpad[:live.size] = live
    vmask = np.zeros(P, bool)
    vmask[:live.size] = True
    orr, svr, cr, tyr = ref.abnormal_ref(t[-1][lpad], top, 1.5, 0.001, k,
                                         valid=vmask)
    cuts = [10, 20]
    with enable_x64():
        o, sv, c, ty = ops.fused_abnormal(
            [jnp.asarray(b) for b in np.split(t[-1], cuts, axis=0)],
            jnp.asarray(top), 1.5, 0.001, k, live=jnp.asarray(lpad),
            valid=jnp.asarray(vmask), interpret=interpret)
    np.testing.assert_array_equal(np.asarray(o), orr)
    assert int(c) == cr
    np.testing.assert_array_equal(np.asarray(ty), tyr)


def test_f32_parity_within_1e4(monkeypatch):
    """Accelerator-native precision: f32 fused results track the f64
    oracle to 1e-4; the flag set and winner order stay identical (the
    fixture keeps scores clear of the thresholds)."""
    monkeypatch.setenv("SCALANA_DETECT_F32", "1")
    t, var, present, scales, top = _case(seed=6, dtype=np.float32)
    t64, var64 = t.astype(np.float64), var.astype(np.float64)
    tmax = float(t64[-1][:, top].max(axis=0, initial=0.0).sum())
    Mr, slr, shr, flr = ref.non_scalable_ref(scales, t64, var64, present,
                                             total_max=tmax, **ARGS)
    orr, _, cr, tyr = ref.abnormal_ref(t64[-1], top, 1.5, 0.001, 9)
    for interpret, tag in MODES:
        M, sl, sh, fl = ops.fused_non_scalable(
            jnp.asarray(t), jnp.asarray(var),
            jnp.asarray(np.log(np.asarray(scales, np.float32))),
            jnp.asarray(present), total_max=tmax,
            interpret=interpret, **ARGS)
        np.testing.assert_allclose(np.asarray(M), Mr, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(sl), slr, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_array_equal(np.asarray(fl), flr)
        o, _, c, ty = ops.fused_abnormal([jnp.asarray(t[-1])],
                                         jnp.asarray(top), 1.5, 0.001, 9,
                                         interpret=interpret)
        np.testing.assert_array_equal(np.asarray(o), orr)
        assert int(c) == cr
        np.testing.assert_allclose(np.asarray(ty), tyr, rtol=1e-4,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret,tag", MODES)
def test_abnormal_empty_flag_set(interpret, tag):
    """A perfectly uniform fleet flags nothing: count 0, every top-k
    slot holds the -inf-score tail in ascending vid-major order."""
    t = np.full((8, 5), 0.25)
    with enable_x64():
        o, sv, c, ty = ops.fused_abnormal(
            [jnp.asarray(t)], jnp.asarray(np.array([0, 1], np.int32)),
            1.5, 0.001, 4, interpret=interpret)
    assert int(c) == 0
    orr, svr, cr, _ = ref.abnormal_ref(t, np.array([0, 1]), 1.5, 0.001, 4)
    assert cr == 0
    np.testing.assert_array_equal(np.asarray(o), orr)
    np.testing.assert_array_equal(np.asarray(ty), np.full(5, 0.25))


def test_abnormal_k_zero_and_empty_entry_point():
    with enable_x64():
        o, sv, c, ty = ops.fused_abnormal([jnp.ones((4, 3))], None,
                                          1.5, 0.001, 0, step_time=1.0)
    assert o.shape == (0,) and int(c) == 0


@pytest.mark.parametrize("interpret,tag", MODES)
def test_all_dead_scale_keeps_finite(interpret, tag):
    """A scale whose every reading is zero (present vertices included)
    must produce the oracle's p0/mean fallbacks, zero share and no
    flags — never inf/nan (the unguarded-divide regression)."""
    t, var, present, scales, top = _case(seed=7)
    t[-1] = 0.0                                    # final scale all-dead
    Mr, slr, shr, flr = ref.non_scalable_ref(scales, t, var, present,
                                             top=top, **ARGS)
    assert not flr.any()
    with enable_x64():
        M, sl, sh, fl = ops.fused_non_scalable(
            jnp.asarray(t), jnp.asarray(var),
            jnp.asarray(np.log(np.asarray(scales, np.float64))),
            jnp.asarray(present), top_idx=jnp.asarray(top),
            interpret=interpret, **ARGS)
    assert np.isfinite(np.asarray(M)).all()
    assert np.isfinite(np.asarray(sl)).all()
    np.testing.assert_allclose(np.asarray(sh), shr, rtol=0, atol=1e-12)
    assert not np.asarray(fl).any()


def test_fused_live_path_no_retrace_across_live_set_sizes():
    """A flapping host hits ONE compiled fused executable: the live
    gather is padded to the fleet size, so traced shapes depend only on
    P."""
    t, var, present, scales, top = _case(seed=8)
    P = t.shape[1]
    with enable_x64():
        blocks = [jnp.asarray(t[-1])]
        topj = jnp.asarray(top)

        def run(n_dead):
            live = np.arange(P - n_dead, dtype=np.int32)
            lpad = np.zeros(P, np.int32)
            lpad[:live.size] = live
            vmask = np.zeros(P, bool)
            vmask[:live.size] = True
            return ops.fused_abnormal(blocks, topj, 1.5, 0.001, 5,
                                      live=jnp.asarray(lpad),
                                      valid=jnp.asarray(vmask))

        run(1)
        baseline = ops._ab_jnp._cache_size()
        for n_dead in (2, 5, 9, 3):
            run(n_dead)
        assert ops._ab_jnp._cache_size() == baseline


# ---------------------------------------------------------------------------
# the view entry points against the numpy detectors
# ---------------------------------------------------------------------------

def _sharded_series(scales=(4, 8, 32), n_hosts=4, straggler=(3, 2, 6.0)):
    g = _step_psg(max(scales))
    p, vid, factor = straggler

    def base(proc, v, n):
        extra = factor * 0.01 if (proc, v) == (p, vid) else 0.0
        return 0.01 * (1 + proc % 3) + 0.001 * v + 0.02 / n + extra

    return g, {n: simulate(g, n, lambda pr, v, n=n: base(pr, v, n),
                           shards=min(n_hosts, n)).ppg for n in scales}


def test_view_entry_points_match_numpy():
    """The device-fed view entry points agree with the numpy detectors:
    flags, merged times, slopes and shares; abnormal winners, ranking
    and typicals on the full fleet and on a live-row subset."""
    g, series = _sharded_series()
    scales = sorted(series)
    ref_ppg = series[scales[-1]]
    V = len(g.vertices)
    top = g.children(g.root)
    present = np.ones((len(scales), V), bool)
    views = [series[n].device_view() for n in scales]
    for kw in (dict(ideal_slope=0.0, slope_margin=0.05, min_share=0.0),
               dict(ideal_slope=-1.0, slope_margin=0.35, min_share=0.0)):
        M, slope, share, flagged = detect_jax.non_scalable_views(
            scales, views, V, present, top, kw["ideal_slope"],
            kw["slope_margin"], kw["min_share"], "mean")
        want = detect_non_scalable(series, backend="numpy",
                                   strategy="mean", top_k=V, **kw)
        assert want
        assert sorted(d.vid for d in want) == \
            np.flatnonzero(flagged).tolist()
        for d in want:
            np.testing.assert_allclose(slope[d.vid], d.slope,
                                       rtol=0, atol=1e-12)
            np.testing.assert_allclose(share[d.vid], d.share,
                                       rtol=0, atol=1e-12)
            np.testing.assert_allclose(
                M[:, d.vid], [d.times[n] for n in scales],
                rtol=0, atol=1e-12)

    for live_rows in (None, np.arange(1, ref_ppg.n_procs - 2)):
        mask = None
        if live_rows is not None:
            mask = np.zeros(ref_ppg.n_procs, bool)
            mask[live_rows] = True
        vids, procs, typical, _ = detect_jax.abnormal_topk_view(
            ref_ppg.device_view(), V, top, 1.5, 0.001, 8,
            live_rows=live_rows)
        want = detect_abnormal(ref_ppg, abnorm_thd=1.5, min_share=0.001,
                               top_k=8, backend="numpy", proc_mask=mask)
        assert want
        if live_rows is not None:
            procs = live_rows[procs]             # live-local -> global
        assert list(zip(vids.tolist(), procs.tolist())) == \
            [(a.vid, a.proc) for a in want]
        np.testing.assert_allclose(typical[vids],
                                   [a.typical for a in want],
                                   rtol=0, atol=1e-12)


# ---------------------------------------------------------------------------
# the historical-scale device cache
# ---------------------------------------------------------------------------

def test_steady_state_detect_dirty_rows_only_and_two_launches():
    """THE acceptance criterion, asserted via the counter seams: with
    all scales resident and a 16-row dirty write on the live scale, one
    full detect cycle (non-scalable + abnormal) uploads ONLY those 16
    rows and launches exactly 2 fused kernels — the historical merged
    columns are reused from the device cache, not recomputed."""
    g, series = _sharded_series()
    scales = sorted(series)
    live_ppg = series[scales[-1]]

    # warm-up: caches fill (one merge_column per historical scale)
    ops.reset_launch_counts()
    detect_non_scalable(series, backend="jax", min_share=0.0)
    detect_abnormal(live_ppg, backend="jax")
    assert ops.launch_counts["merge_column"] == len(scales) - 1
    hist_views = [series[n].device_view() for n in scales[:-1]]
    live_view = live_ppg.device_view()
    for v in hist_views:
        assert v.merged_column() is not None       # cache populated

    # a second clean detect: zero uploads, zero re-merges, <= 2 launches
    ops.reset_launch_counts()
    detect_non_scalable(series, backend="jax", min_share=0.0)
    detect_abnormal(live_ppg, backend="jax")
    assert dict(ops.launch_counts) == {"non_scalable_live": 1,
                                       "abnormal": 1}
    assert live_view.last_upload_rows == 0

    # 16-row dirty write on the LIVE scale only
    rows = np.arange(7, 23)
    live_ppg.perf.set_entries(rows, 2, 0.5)
    ops.reset_launch_counts()
    ns = detect_non_scalable(series, backend="jax", min_share=0.0)
    assert live_view.last_upload_rows == rows.size  # dirty rows only
    assert dict(ops.launch_counts) == {"non_scalable_live": 1}
    ab = detect_abnormal(live_ppg, backend="jax")
    assert live_view.last_upload_rows == 0     # already clean
    assert dict(ops.launch_counts) == {"non_scalable_live": 1,
                                       "abnormal": 1}   # <= 2 launches
    for v in hist_views:
        assert v.last_upload_rows == 0             # historical: untouched
        assert v.merged_column() is not None

    # and the answers still match the numpy reference after the write
    assert any(a.vid == 2 for a in ab)             # the write is visible
    assert _ab_key(ab) == _ab_key(detect_abnormal(live_ppg,
                                                  backend="numpy"))
    assert [d.vid for d in ns] == \
        [d.vid for d in detect_non_scalable(series, backend="numpy",
                                            min_share=0.0)]


def test_historical_write_invalidates_exactly_that_column():
    """A write to ONE historical scale bumps its revision and refills
    only its merged column on the next detect."""
    _, series = _sharded_series()
    scales = sorted(series)
    detect_non_scalable(series, backend="jax", min_share=0.0)
    victim = series[scales[0]]
    other = series[scales[1]]
    rev = victim.device_view().revision
    victim.perf.set_entry(1, 1, 9.0)
    ops.reset_launch_counts()
    detect_non_scalable(series, backend="jax", min_share=0.0)
    assert victim.device_view().revision == rev + 1
    assert ops.launch_counts["merge_column"] == 1  # only the victim
    assert other.device_view().merged_column() is not None
    # stale column never served: the new reading lands in the result
    M, _, _, _ = detect_jax.non_scalable_views(
        scales, [series[n].device_view() for n in scales],
        len(victim.psg.vertices), np.ones((3, len(victim.psg.vertices)),
                                          bool),
        victim.psg.children(victim.psg.root), 0.0, 0.05, 0.0, "max")
    assert M[0, 1] == 9.0


def test_dtype_flip_invalidates_all_columns(monkeypatch):
    """SCALANA_DETECT_F32 mid-run: every view re-pins in full and every
    merged column refills — no stale f64 column feeds an f32 stack."""
    _, series = _sharded_series(scales=(4, 8, 16))
    detect_non_scalable(series, backend="jax", min_share=0.0)
    monkeypatch.setenv("SCALANA_DETECT_F32", "1")
    ops.reset_launch_counts()
    detect_non_scalable(series, backend="jax", min_share=0.0)
    assert ops.launch_counts["merge_column"] == len(series) - 1
    for n in sorted(series)[:-1]:
        col = series[n].device_view().merged_column()
        assert col is not None and col.dtype == jnp.float32


def test_kernel_launch_counter_on_views():
    """``launch_counts`` over three detect cycles: one cache fill per
    historical scale in all, one non-scalable and one abnormal launch
    on the live scale every cycle."""
    _, series = _sharded_series(scales=(4, 8, 16))
    scales = sorted(series)
    ops.reset_launch_counts()
    for _ in range(3):
        detect_non_scalable(series, backend="jax", min_share=0.0)
        detect_abnormal(series[scales[-1]], backend="jax")
    assert dict(ops.launch_counts) == {"merge_column": len(scales) - 1,
                                       "non_scalable_live": 3,
                                       "abnormal": 3}
    for n in scales[:-1]:                   # each filled its own column
        assert series[n].device_view().merged_column() is not None
