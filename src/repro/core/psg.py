"""Static PSG construction from a jaxpr (the paper's compile-time analysis).

The jaxpr is the compiler IR of a JAX program: ``scan``/``while`` map to the
paper's Loop vertices, ``cond`` to Branch, inlined calls (``pjit``,
``custom_*``, ``remat``) to Call — inter-procedural analysis is literal
sub-jaxpr recursion.  Collective primitives (visible under ``shard_map``)
become Comm vertices directly; for pjit-partitioned programs Comm vertices
are added from the compiled HLO by ``repro.core.commdep.annotate_from_hlo``.

Data-dependence edges are true def-use edges between vertices at the same
nesting level; control edges connect a control vertex to its children.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.extend.core import Var as _JaxVar

from repro.core import costs
from repro.core.graph import (
    BRANCH, CALL, COMM, COMP, LOOP, ROOT,
    COLLECTIVE_PRIMS, P2P_PRIMS, PSG, Vertex,
)

# primitives whose sub-jaxpr we inline as a Call vertex
_CALL_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")
_CALL_PRIMS = {
    "pjit", "closed_call", "core_call", "remat", "checkpoint", "remat2",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call_jaxpr", "custom_lin", "shard_map", "jit",
}
_LOOP_PRIMS = {"scan", "while"}


def _source_of(eqn) -> str:
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    return f"{frame.file_name}:{frame.start_line}" if frame else ""


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(label, jaxpr) pairs for an eqn's nested jaxprs."""
    out = []
    name = eqn.primitive.name
    if name == "scan":
        out.append(("body", eqn.params["jaxpr"]))
    elif name == "while":
        out.append(("cond", eqn.params["cond_jaxpr"]))
        out.append(("body", eqn.params["body_jaxpr"]))
    elif name == "cond":
        for i, br in enumerate(eqn.params["branches"]):
            out.append((f"branch{i}", br))
    else:
        for key in _CALL_PARAM_KEYS:
            if key in eqn.params:
                out.append((key, eqn.params[key]))
                break
    return [(lbl, j) for lbl, j in out if j is not None]


def _raw(jaxpr):
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _comm_bytes(eqn) -> float:
    return float(sum(
        int(np.prod(v.aval.shape, dtype=np.int64)) * v.aval.dtype.itemsize
        for v in eqn.invars if hasattr(v, "aval") and hasattr(v.aval, "shape")))


def _trip_count(eqn) -> int:
    if eqn.primitive.name == "scan":
        return int(eqn.params.get("length", 1))
    return 1   # while: unknown statically


class _Builder:
    def __init__(self, max_depth: int = 64):
        self.psg = PSG()
        root = self.psg.new_vertex(ROOT, "root")
        self.psg.root = root.vid
        self.max_depth = max_depth

    # ------------------------------------------------------------------
    def walk(self, jaxpr, parent: int, depth: int,
             var_def: Optional[Dict[Any, int]] = None) -> None:
        """One nesting level. var_def maps jaxpr Var -> producing vertex."""
        jaxpr = _raw(jaxpr)
        var_def = dict(var_def or {})
        prev_vid: Optional[int] = None
        for eqn in jaxpr.eqns:
            v = self._vertex_for(eqn, parent, depth)
            # true def-use data edges at this level (Literals are not Vars)
            producers = {var_def[iv] for iv in eqn.invars
                         if isinstance(iv, _JaxVar)
                         and iv in var_def and var_def[iv] != v.vid}
            for p in producers:
                self.psg.add_edge(p, v.vid, "data")
            if not producers and prev_vid is not None:
                # fall back to program order so the chain stays connected
                self.psg.add_edge(prev_vid, v.vid, "data")
            for ov in eqn.outvars:
                var_def[ov] = v.vid
            self.psg.add_edge(parent, v.vid, "control")
            prev_vid = v.vid
            # recurse
            if v.is_control and depth < self.max_depth:
                for lbl, sub in _sub_jaxprs(eqn):
                    self.walk(sub, v.vid, depth + 1)
                # roll nested static counters up into the control vertex
                self._rollup(v, _trip_count(eqn))

    # ------------------------------------------------------------------
    def _vertex_for(self, eqn, parent: int, depth: int) -> Vertex:
        name = eqn.primitive.name
        src = _source_of(eqn)
        if name in _LOOP_PRIMS:
            return self.psg.new_vertex(
                LOOP, name, source=src, parent=parent, depth=depth,
                meta={"trip_count": _trip_count(eqn)})
        if name == "cond":
            return self.psg.new_vertex(BRANCH, name, source=src,
                                       parent=parent, depth=depth)
        if name in _CALL_PRIMS and any(k in eqn.params
                                       for k in _CALL_PARAM_KEYS):
            label = eqn.params.get("name", name)
            return self.psg.new_vertex(CALL, f"{name}:{label}", source=src,
                                       parent=parent, depth=depth)
        if name in COLLECTIVE_PRIMS:
            v = self.psg.new_vertex(COMM, name, source=src, parent=parent,
                                    depth=depth)
            v.comm_kind = "all_reduce" if name in ("psum", "pmax", "pmin") \
                else name
            v.comm_bytes = _comm_bytes(eqn)
            if name in P2P_PRIMS:
                v.p2p_pairs = [tuple(p) for p in eqn.params.get("perm", [])]
            return v
        flops, nbytes = costs.eqn_costs(eqn)
        v = self.psg.new_vertex(COMP, name, source=src, parent=parent,
                                depth=depth)
        v.prims = [name]
        v.flops, v.bytes = flops, nbytes
        return v

    def _rollup(self, v: Vertex, trips: int) -> None:
        kids = self.psg.children(v.vid)
        v.flops = trips * sum(self.psg.vertices[c].flops for c in kids)
        v.bytes = trips * sum(self.psg.vertices[c].bytes for c in kids)
        v.comm_bytes = trips * sum(self.psg.vertices[c].comm_bytes
                                   for c in kids)


def build_psg(fn=None, *args, jaxpr=None, max_depth: int = 64, **kwargs) -> PSG:
    """Static analysis: trace ``fn(*args)`` (or take a ready jaxpr) -> PSG."""
    if jaxpr is None:
        jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    b = _Builder(max_depth=max_depth)
    b.walk(jaxpr, parent=b.psg.root, depth=0)
    return b.psg


def top_level_order(psg: PSG) -> List[int]:
    """Program-order vids directly under the root (children index is
    maintained in creation = program order)."""
    return psg.children(psg.root)
