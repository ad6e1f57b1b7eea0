"""Backtracking root-cause detection (paper §IV-B, Algorithm 1).

All edges are traversed in reverse (dependence direction).  From each
problematic vertex we walk backward:

  * at a p2p Comm vertex with a waiting event — follow the inter-process
    communication-dependence edge to the partner process (edges without a
    waiting event are pruned, the paper's search-space optimization);
  * at an unscanned Loop/Branch vertex — follow the control-dependence edge
    into the structure (continue from its *end* vertex);
  * otherwise — follow the data-dependence edge to the predecessor (the
    max-time predecessor when several exist);
  * stop at the root or at a collective-communication vertex, except a
    collective *start* vertex, where the walk jumps to the process whose
    late arrival everyone waited on.

The result is a set of causal paths over (process, vertex) pairs whose
endpoints are the root-cause candidates, reported with source locations.

One engine walks them: :func:`backtrack` runs Algorithm 1 Main(), one
sequential :func:`backtrack_one` walk per start node, in start order.
Each walk skips nodes already scanned by an earlier path (the paper's
``scanned`` set), so overlapping causal paths are walked once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.detect import Abnormal, NonScalable
from repro.core.graph import BRANCH, CALL, COMM, LOOP, PPG, PSG
from repro.core.spans import spanned

Node = Tuple[int, int]                     # (proc, vid)

WAIT_COUNTER = "wait_s"
WAIT_EPS = 1e-9


@dataclasses.dataclass
class Path:
    nodes: List[Node]
    start_reason: str                      # "non_scalable" | "abnormal"

    @property
    def root_cause(self) -> Node:
        return self.nodes[-1]

    def __iter__(self):
        return iter(self.nodes)


class _SeenUnion:
    """Non-copying membership view over (scanned, own-path) node sets.

    ``backtrack_one`` used to rebuild ``scanned | set(path)`` on every
    step — an O(|scanned| + |path|) copy per node that goes quadratic
    when hundreds of overlapping paths are walked over a large scanned
    set.  The walk only ever asks ``node in visited``, so a
    chained-membership wrapper over the two live sets (the path set
    updated incrementally on append) is semantically identical and O(1)
    per probe."""

    __slots__ = ("scanned", "path")

    def __init__(self, scanned: Set[Node], path: Set[Node]):
        self.scanned = scanned
        self.path = path

    def __contains__(self, node) -> bool:
        return node in self.scanned or node in self.path


def _wait_of(ppg: PPG, node: Node) -> float:
    return ppg.perf.counter_at(WAIT_COUNTER, *node)


def _is_collective(psg: PSG, vid: int) -> bool:
    v = psg.vertices[vid]
    return v.kind == COMM and not v.p2p_pairs


def _is_p2p(psg: PSG, vid: int) -> bool:
    v = psg.vertices[vid]
    return v.kind == COMM and bool(v.p2p_pairs)


def _data_pred(ppg: PPG, node: Node, visited) -> Optional[Node]:
    proc, vid = node
    preds = ppg.psg.preds(vid, "data")
    cands = [(proc, p) for p in preds if (proc, p) not in visited]
    if not cands:
        return None
    return max(cands, key=lambda n: ppg.get_time(*n))


def _control_end(ppg: PPG, node: Node, visited) -> Optional[Node]:
    """Continue from the end (last child) of a Loop/Branch structure."""
    proc, vid = node
    kids = ppg.psg.children(vid)
    for k in reversed(kids):
        if (proc, k) not in visited:
            return (proc, k)
    return None


def _comm_partner(ppg: PPG, node: Node, visited) -> Optional[Node]:
    partners = [p for p in ppg.comm_partners(*node) if p not in visited]
    if not partners:
        return None
    # the cause is the partner we waited for: latest/most loaded one
    return max(partners, key=lambda n: ppg.get_time(*n))


def _latest_participant(ppg: PPG, node: Node) -> Optional[Node]:
    """For a collective start vertex: the process everyone waited on —
    the participant with the smallest wait (it arrived last)."""
    proc, vid = node
    group = [p for p in ppg.comm_partners(proc, vid)] + [node]
    if len(group) <= 1:
        return None
    return min(group, key=lambda n: _wait_of(ppg, n))


def backtrack_one(ppg: PPG, start: Node, *, reason: str,
                  scanned: Set[Node], max_len: int = 256) -> Path:
    psg = ppg.psg
    path: List[Node] = []
    path_set: Set[Node] = set()
    # visited == scanned | set(path) at every step, without the per-step
    # union copy (quadratic over many overlapping paths)
    visited = _SeenUnion(scanned, path_set)
    v: Optional[Node] = start
    first = True
    while v is not None and len(path) < max_len:
        proc, vid = v
        vert = psg.vertices[vid]
        if vert.kind == "Root":
            break
        if _is_collective(psg, vid) and not first:
            path.append(v)                  # terminal collective
            break
        path.append(v)
        path_set.add(v)
        nxt: Optional[Node] = None
        if _is_collective(psg, vid):        # collective start vertex
            late = _latest_participant(ppg, v)
            if late is not None and late not in visited:
                nxt = _data_pred(ppg, late, visited) or late
            else:
                nxt = _data_pred(ppg, v, visited)
        elif _is_p2p(psg, vid):
            if _wait_of(ppg, v) > WAIT_EPS:     # pruning: only waiting edges
                nxt = _comm_partner(ppg, v, visited)
            if nxt is None:
                nxt = _data_pred(ppg, v, visited)
        elif vert.kind in (LOOP, BRANCH, CALL) and v not in scanned:
            nxt = _control_end(ppg, v, visited) or _data_pred(ppg, v, visited)
        else:
            nxt = _data_pred(ppg, v, visited)
        first = False
        v = nxt
    scanned.update(path)
    return Path(nodes=path, start_reason=reason)


def _start_nodes(ppg: PPG, non_scalable: Sequence[NonScalable],
                 abnormal: Sequence[Abnormal]) -> List[Tuple[Node, str]]:
    """Algorithm 1 Main()'s start order: non-scalable vertices (walked from
    their slowest process) first, then abnormal (proc, vertex) pairs."""
    tm = ppg.times_matrix()
    starts: List[Tuple[Node, str]] = []
    for n in non_scalable:
        proc = int(tm[:, n.vid].argmax()) if tm.size else 0
        starts.append(((proc, n.vid), "non_scalable"))
    for a in abnormal:
        starts.append(((a.proc, a.vid), "abnormal"))
    return starts


@spanned("backtrack")
def backtrack(ppg: PPG, non_scalable: Sequence[NonScalable],
              abnormal: Sequence[Abnormal]) -> List[Path]:
    """Algorithm 1 Main(): non-scalable starts first, then unscanned
    abnormal vertices, one sequential scalar walk per start node."""
    scanned: Set[Node] = set()
    paths: List[Path] = []
    for node, reason in _start_nodes(ppg, non_scalable, abnormal):
        if reason == "abnormal" and node in scanned:
            continue
        p = backtrack_one(ppg, node, reason=reason, scanned=scanned)
        if p.nodes:
            paths.append(p)
    return paths


def _anomaly_score(ppg: PPG, node: Node,
                   busy: Optional[np.ndarray] = None) -> float:
    """BUSY time above the cross-process typical for this vertex.

    A propagated delay leaves every downstream vertex time-NORMAL (they
    run at base speed, just later) and surfaces as WAITING at comm
    vertices — which are symptoms, not causes.  Scoring busy time
    (time - wait) makes the most anomalous node on a causal path the
    worker that actually ran long, i.e. the root-cause candidate.

    ``busy`` is the precomputed (n_procs, V) time-minus-wait matrix; pass
    it when scoring many nodes so each call is one column reduction."""
    if node not in ppg.perf:
        return 0.0
    if busy is None:
        busy = _busy_matrix(ppg)
    proc, vid = node
    col = busy[:, vid]
    mine = float(col[proc])
    others = np.sort(col[col > 0.0])           # unset entries are 0: excluded
    if others.size == 0:
        return mine
    return mine - float(others[others.size // 2])


def _wait_matrix(ppg: PPG) -> np.ndarray:
    """Dense (n_procs, V) ``wait_s`` (0.0 where unset) from the compressed
    counter columns — works unchanged on sharded stores, whose
    ``counter_columns`` is the stacked per-host view."""
    n = len(ppg.psg.vertices)
    out = np.zeros((ppg.n_procs, n))
    vids, values, mask = ppg.perf.counter_columns(WAIT_COUNTER)
    keep = vids < n
    if keep.any():
        out[:, vids[keep]] = np.where(mask[:, keep], values[:, keep], 0.0)
    return out


def _busy_matrix(ppg: PPG) -> np.ndarray:
    """time minus wait, (n_procs, V) — expanded from the column-sparse
    ``wait_s`` counter (see :func:`_wait_matrix`)."""
    return ppg.times_matrix() - _wait_matrix(ppg)


@spanned("root_causes")
def root_causes(paths: Sequence[Path], psg: PSG, top_k: int = 5,
                ppg: Optional[PPG] = None) -> List[Tuple[Node, str, str]]:
    """Deduplicated root-cause vertices (node, name, source).

    With a PPG, each path contributes its most ANOMALOUS node (see
    _anomaly_score); without perf data, its terminal node (the paper's
    raw Algorithm-1 endpoint).  Ranked by path count, then score."""
    counts: Dict[Node, int] = {}
    scores: Dict[Node, float] = {}
    busy = _busy_matrix(ppg) if ppg is not None else None
    memo: Dict[Node, float] = {}

    def score(n: Node) -> float:
        if n not in memo:
            memo[n] = _anomaly_score(ppg, n, busy)
        return memo[n]

    for p in paths:
        if ppg is not None and p.nodes:
            node = max(p.nodes, key=score)
            scores[node] = max(scores.get(node, 0.0), score(node))
        else:
            node = p.root_cause
        counts[node] = counts.get(node, 0) + 1
    ranked = sorted(counts,
                    key=lambda n: (-counts[n], -scores.get(n, 0.0)))[:top_k]
    out = []
    for node in ranked:
        v = psg.vertices[node[1]]
        out.append((node, v.name, v.source))
    return out
