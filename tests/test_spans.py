"""The program's spans (``repro.core.spans``).

A real ``jax.profiler`` trace on the CPU around one smoke-size diagnosis
cycle (detection kernels in Pallas interpret mode, the code path a chip
runs), one sampled plus one compiled train step, and one fused op handed
several row blocks: every name in ``NAMES`` is emitted, nested as the
layers nest, with its stats.  The
names stay clear of the chip benchmark's own spans, and the analysis
layer keeps importing and running without jax."""
import collections
import importlib.util
import os
import re
import subprocess
import sys
import textwrap

import pytest

jax = pytest.importorskip("jax")

from repro.core import spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# span -> the spans one of which must hold it (None: none may)
PARENTS = {
    "trainer.step": None,
    "trainer.batch": {"trainer.step"},
    "profiler.compiled_step": {"trainer.step"},
    "profiler.sampled_step": {"trainer.step"},
    "profiler.fence": {"profiler.sampled_step"},
    "store.apply_rows": None,
    "detect.non_scalable": None,
    "detect.abnormal": None,
    "feed.refresh": {"detect.non_scalable", "detect.abnormal"},
    # only a caller handing a fused op several row blocks concatenates;
    # detection's device views hand it one resident (P, V) buffer
    "detect.concat": None,
    "detect.readback": {"detect.non_scalable", "detect.abnormal"},
    "backtrack": None,
    "root_causes": {None, "report.render"},
    "store.stack": {"backtrack", "root_causes", "report.render"},
    "report.render": None,
}
STATS = {
    "trainer.step": {"step"},
    "profiler.sampled_step": {"eqns"},
    "profiler.fence": {"vid"},
    "store.apply_rows": {"rows"},
    "feed.refresh": {"blocks", "dirty_blocks", "rows", "bytes", "full",
                     "scatters"},
    "detect.concat": {"operands"},
    "store.stack": {"shards", "restacked"},
    "detect.abnormal": {"col_tiles"},
}


def _cycle_inputs():
    from repro.core.inject import simulate
    from tests.test_device_detect import _step_psg
    g = _step_psg(32)

    def base(proc, v, n):
        return 0.01 * (1 + proc % 3) + 0.001 * v + 0.02 / n \
            + (0.06 if (proc, v) == (3, 2) else 0.0)

    series = {n: simulate(g, n, lambda p, v, n=n: base(p, v, n),
                          shards=4).ppg for n in (8, 16, 32)}
    return g, series


def _diagnose(series):
    from repro.core import (backtrack, detect_abnormal, detect_non_scalable,
                            render_report, root_causes)
    live = series[max(series)]
    shard = live.perf.shards[0]
    shard.apply_rows(shard.extract_rows([0, 1]))
    ns = detect_non_scalable(series, backend="jax", min_share=0.0)
    ab = detect_abnormal(live, backend="jax")
    paths = backtrack(live, ns, ab)
    root_causes(paths, live.psg, ppg=live)
    return render_report(live, ns, ab, paths)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.kernels.detect_fused import ops
    from repro.training import Trainer

    cfg = get_smoke("mamba2-130m")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "kernel_mode", lambda interpret=None: "interpret")
        mp.setenv("SCALANA_DETECT_F32", "1")
        tr = Trainer(RunConfig(arch=cfg.name, total_steps=4, warmup_steps=1,
                               scalana_sample_every=2),
                     arch_cfg=cfg, shape=ShapeConfig("spans", 16, 2, "train"))
        state = tr.train(num_steps=1)             # compiled, outside
        _, series = _cycle_inputs()
        _diagnose(series)                          # compiles, full upload
        out = str(tmp_path_factory.mktemp("trace"))
        blocks = [jnp.ones((2, 4), jnp.float32)] * 2
        with jax.profiler.trace(out):
            tr.train(num_steps=2, state=state)     # sampled, then compiled
            report = _diagnose(series)
            ops.fused_abnormal(blocks, None, 1.5, 0.01, 2, step_time=1.0)
    assert "Root causes" in report
    path = next(os.path.join(d, f) for d, _, fs in os.walk(out)
                for f in fs if f.endswith(".xplane.pb"))
    lines = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("scalana."):
                    lines[(plane.name, line.name)].append(
                        (ev.start_ns, ev.end_ns, ev.name[len("scalana."):],
                         {k for k, _ in ev.stats}))
    return lines


def _events(recorded):
    return [ev for evs in recorded.values() for ev in evs]


def test_every_name_is_emitted_with_its_stats(recorded):
    seen = collections.defaultdict(set)
    for _, _, name, stats in _events(recorded):
        seen[name] |= stats
    assert set(seen) == set(spans.NAMES) == set(PARENTS)
    for name, want in STATS.items():
        assert want <= seen[name], (name, seen[name])


def test_spans_nest_as_the_layers_do(recorded):
    for evs in recorded.values():
        for s, e, name, _ in evs:
            holders = {n for s2, e2, n, _ in evs
                       if (s2, e2, n) != (s, e, name) and s2 <= s and e <= e2}
            allowed = PARENTS[name]
            if allowed is None:
                assert not holders, (name, holders)
            elif None in allowed:
                assert holders <= allowed, (name, holders)
            else:
                assert holders & allowed, (name, holders)


def test_abnormal_span_counts_two_column_tiles_above_128_vertices(tmp_path):
    from jax.profiler import ProfileData
    from repro.core import detect_abnormal
    from repro.core.inject import simulate
    from repro.kernels.detect_fused import ops
    from tests.test_device_detect import _step_psg
    g = _step_psg(16, n_comp=140)
    assert len(g.vertices) > 128
    ppg = simulate(g, 16, lambda p, v: 0.01 + 0.001 * v
                   + (1.0 if (p, v) == (3, 2) else 0.0), shards=2).ppg
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "kernel_mode", lambda interpret=None: "interpret")
        mp.setenv("SCALANA_DETECT_F32", "1")
        detect_abnormal(ppg, backend="jax")            # compiles outside
        with jax.profiler.trace(str(tmp_path)):
            found = detect_abnormal(ppg, backend="jax")
    assert (3, 2) in {(a.proc, a.vid) for a in found}
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    tiles = [dict(ev.stats).get("col_tiles")
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "scalana.detect.abnormal"]
    assert tiles == [2]


def test_store_stack_counts_the_shards_written_since_the_last_read(tmp_path):
    """Over 64 shards, a stacked read after writes to k of them copies
    those k (the span's stat ``restacked``), and the read after it none;
    the first read stacks all 64."""
    from jax.profiler import ProfileData
    from repro.core.shard import ShardedStore, shard_ranges
    store = ShardedStore(shard_ranges(256, 64), 8)
    store.set_column(2, 1.0, counters={"wait_s": 0.5})
    ks = (3, 17, 64)
    with jax.profiler.trace(str(tmp_path)):
        store.time_matrix()
        store.counter_columns("wait_s")
        for k in ks:
            for p in range(0, 4 * k, 4):
                store.set_entry(p, 2, 0.25 * k, counters={"wait_s": 0.125})
            for _ in range(2):
                store.time_matrix()
            for _ in range(2):
                store.counter_columns("wait_s")
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    reads = sorted((ev.start_ns, dict(ev.stats))
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for ev in line.events
                   if ev.name == "scalana.store.stack")
    assert [st["restacked"] for _, st in reads] == \
        [64, 64] + [n for k in ks for n in (k, 0, k, 0)]
    assert all(st["shards"] == 64 for _, st in reads)


def test_shared_block_scope_reaches_the_train_steps_hlo():
    """Every op of a shared-block call carries ``hybrid.shared_block``
    in its name stack, which the HLO keeps as op metadata and the device
    trace as each op's name stack."""
    import jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.training import Trainer
    assert spans.SCOPES == ("hybrid.shared_block",)
    cfg = get_smoke("zamba2-2.7b")
    tr = Trainer(RunConfig(arch=cfg.name, scalana=False), arch_cfg=cfg,
                 shape=ShapeConfig("scope", 16, 2, "train"))
    state = jax.eval_shape(tr.init_state)
    tokens = jax.ShapeDtypeStruct((2, 17), jnp.int32)
    hlo = jax.jit(tr.train_step_fn).lower(
        state, {"tokens": tokens}).compile().as_text()
    named = re.findall(r'op_name="([^"]*hybrid\.shared_block[^"]*)"', hlo)
    assert any(n.endswith("dot_general") for n in named)
    assert any(n.startswith("jit(train_step)/jvp(") for n in named)
    assert any("transpose(" in n for n in named)       # the backward too
    assert any("rematted_computation" in n for n in named)   # recompute
    with pytest.raises(ValueError):
        spans.scope("hybrid.other_block")


def test_names_differ_from_the_benchmark_spans():
    spec = importlib.util.spec_from_file_location(
        "chipbench_harness", os.path.join(REPO, "chipbench", "harness.py"))
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    emitted = {"scalana." + n for n in spans.NAMES}
    assert not emitted & set(harness.SPANS)
    assert len(set(spans.NAMES)) == len(spans.NAMES)


def test_core_imports_and_spans_run_without_jax():
    code = textwrap.dedent("""
        import importlib.abc
        import sys

        class NoJax(importlib.abc.MetaPathFinder):
            def find_spec(self, name, path, target=None):
                if name.split(".")[0] in ("jax", "jaxlib"):
                    raise ImportError(f"{name} is not installed here")

        sys.meta_path.insert(0, NoJax())
        import repro.core
        from repro.core import (backtrack, detect_abnormal,
                                detect_non_scalable, render_report)
        from repro.core.inject import simulate_series
        from repro.core.spans import span
        with span("feed.refresh", blocks=3) as sp:
            sp.set_metadata(rows=2)
        from repro.core import PSG, COMP
        g = PSG()
        g.root = g.new_vertex("Root", "root").vid
        for i in range(3):
            v = g.new_vertex(COMP, f"c{i}", parent=g.root)
            g.add_edge(g.root, v.vid, "control")
        series = simulate_series(g, [2, 4],
                                 lambda p, vid, n: 1.0 / n + (vid == 1))
        ns = detect_non_scalable(series)
        ab = detect_abnormal(series[4])
        render_report(series[4], ns, ab, backtrack(series[4], ns, ab))
        assert "jax" not in sys.modules
        print("jax-free-ok")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "jax-free-ok" in out.stdout
