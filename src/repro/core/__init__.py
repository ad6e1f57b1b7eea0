"""ScalAna core: graph-based scaling-loss detection (the paper's contribution).

Pipeline:  build_psg (static, jaxpr) -> contract -> [GraphProfiler runtime
sampling | annotate_from_hlo comm refinement] -> build_ppg -> detect
(non-scalable + abnormal) -> backtrack (Algorithm 1) -> render_report.

Exports resolve lazily (PEP 562) so the pure-numpy analysis layer (graph /
detect / backtrack / inject / contraction) can be imported without paying
for — or even having — jax, which only the static/profiling channels
(psg.build_psg, GraphProfiler) need.
"""
from typing import TYPE_CHECKING

# export name -> submodule that defines it
_EXPORTS = {
    "Path": "backtrack", "backtrack": "backtrack",
    "backtrack_one": "backtrack", "root_causes": "backtrack",
    "CommLog": "commdep", "add_comm_edges": "commdep",
    "annotate_from_hlo": "commdep",
    "contract": "contraction",
    "Abnormal": "detect", "NonScalable": "detect",
    "MERGE_STRATEGIES": "detect", "JIT_STRATEGIES": "detect",
    "detect_abnormal": "detect", "detect_non_scalable": "detect",
    "fit_loglog": "detect",
    "BRANCH": "graph", "CALL": "graph", "COMM": "graph", "COMP": "graph",
    "LOOP": "graph", "ROOT": "graph",
    "CommIndex": "graph", "CounterColumns": "graph", "EdgeSet": "graph",
    "PPG": "graph", "PSG": "graph",
    "PerfStore": "graph", "PerfVector": "graph", "Vertex": "graph",
    "collective_bytes_total": "hlo", "parse_collectives": "hlo",
    "simulate": "inject", "simulate_series": "inject",
    "p2p_rounds": "inject", "seeded_base_times": "inject",
    "vectorized_base_times": "inject",
    "DeviceShardView": "shard", "PerfShard": "shard",
    "ShardedStore": "shard", "shard_ranges": "shard",
    "build_ppg": "ppg",
    "GraphProfiler": "profiler",
    "build_psg": "psg",
    "render_report": "report",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    module = importlib.import_module(f"{__name__}.{target}")
    value = getattr(module, name)
    globals()[name] = value           # cache: resolve each name once
    return value


def __dir__():
    return __all__


# `backtrack` is the one export whose name collides with its defining
# submodule.  A direct `import repro.core.backtrack` binds the *module*
# onto this package, and because the attribute then exists, __getattr__
# never fires and `from repro.core import backtrack` hands back the
# module instead of the function — silently, and dependent on which
# import ran first.  Pin the function eagerly (the submodule is pure
# numpy, so this costs nothing and keeps the jax-needing channels lazy).
from repro.core.backtrack import backtrack  # noqa: E402


if TYPE_CHECKING:                     # static analyzers see eager imports
    from repro.core.backtrack import (Path, backtrack, backtrack_one,
                                      root_causes)
    from repro.core.commdep import CommLog, add_comm_edges, annotate_from_hlo
    from repro.core.contraction import contract
    from repro.core.detect import (Abnormal, JIT_STRATEGIES,
                                   MERGE_STRATEGIES, NonScalable,
                                   detect_abnormal, detect_non_scalable,
                                   fit_loglog)
    from repro.core.graph import (BRANCH, CALL, COMM, COMP, LOOP, ROOT,
                                  CommIndex, CounterColumns, EdgeSet, PPG,
                                  PSG, PerfStore, PerfVector, Vertex)
    from repro.core.hlo import collective_bytes_total, parse_collectives
    from repro.core.inject import (p2p_rounds, seeded_base_times, simulate,
                                   simulate_series, vectorized_base_times)
    from repro.core.ppg import build_ppg
    from repro.core.profiler import GraphProfiler
    from repro.core.shard import (DeviceShardView, PerfShard, ShardedStore,
                                  shard_ranges)
    from repro.core.psg import build_psg
    from repro.core.report import render_report
