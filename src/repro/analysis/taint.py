"""jaxpr taint analysis for A003 (unsafe approximation sink).

Sources are the *approximate value* leaves of a traced program (memoized
TAF outputs, perforated partial sums). Sinks are positions where a tainted
value steers the PROGRAM rather than flowing through arithmetic:

  * the predicate operand of `cond`/`switch`,
  * the carry positions feeding a `while` loop's cond_jaxpr output,
  * the index operands of `gather` / `dynamic_slice` /
    `dynamic_update_slice` / `scatter*`.

Arithmetic on approximate data is the *point* of approximate computing --
bounded error in, bounded error out. Indices and predicates are different:
a 1-ulp error flips a branch or reads a different row, so the error model
becomes discontinuous. That asymmetry (safe-to-perturb dataflow vs
unsafe-to-perturb control flow) is the classic AC safety condition, and it
is checkable purely on the jaxpr.

The walk is conservative: any tainted input taints every output of an eqn
unless the primitive is handled structurally (jit / cond / while / scan
recurse into their subjaxprs; while/scan carries run to a fixpoint).
Detector STATE (e.g. TAF's `remaining` counter) steering a `cond` is the
approximation *mechanism*, not a defect -- callers control that by choosing
which leaves they mark tainted.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Set

from jax.extend.core import Literal

# sink primitive -> (operand slice holding indices, sink kind)
_INDEX_SINKS = {
    "gather": (slice(1, 2), "gather indices"),
    "dynamic_slice": (slice(1, None), "dynamic_slice start indices"),
    "dynamic_update_slice": (slice(2, None),
                             "dynamic_update_slice start indices"),
    "scatter": (slice(1, 2), "scatter indices"),
    "scatter-add": (slice(1, 2), "scatter indices"),
    "scatter_add": (slice(1, 2), "scatter indices"),
    "scatter-mul": (slice(1, 2), "scatter indices"),
    "scatter-min": (slice(1, 2), "scatter indices"),
    "scatter-max": (slice(1, 2), "scatter indices"),
}


@dataclasses.dataclass(frozen=True)
class TaintSink:
    primitive: str
    kind: str        # "branch predicate" | "while predicate" | "... indices"
    path: str        # subjaxpr path, e.g. "jit/cond[1]"
    eqn_repr: str

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


def _tainted_in(eqn, tainted: Set) -> List[int]:
    return [i for i, v in enumerate(eqn.invars)
            if not isinstance(v, Literal) and v in tainted]


def _walk(jaxpr, tainted: Set, path: str, sinks: List[TaintSink]) -> Set:
    """Propagate taint through one (open) jaxpr; `tainted` holds Var
    objects of this jaxpr's scope. Returns the set of tainted outvars
    (by position index into jaxpr.outvars)."""
    tainted = set(tainted)
    for eqn in jaxpr.eqns:
        hit = _tainted_in(eqn, tainted)
        name = eqn.primitive.name

        if name in _INDEX_SINKS:
            sl, kind = _INDEX_SINKS[name]
            idx_positions = range(*sl.indices(len(eqn.invars)))
            if any(i in hit for i in idx_positions):
                sinks.append(TaintSink(primitive=name, kind=kind, path=path,
                                       eqn_repr=str(eqn)[:200]))

        if name in ("cond", "switch"):
            # invars[0] is the predicate/branch index; the rest are operands.
            if 0 in hit:
                sinks.append(TaintSink(primitive=name,
                                       kind="branch predicate", path=path,
                                       eqn_repr=str(eqn)[:200]))
            branches = eqn.params.get("branches", ())
            out_taint = set()
            for bi, br in enumerate(branches):
                inner = br.jaxpr
                sub = {iv for iv, ov in zip(inner.invars, eqn.invars[1:])
                       if not isinstance(ov, Literal) and ov in tainted}
                touts = _walk(inner, sub, f"{path}/cond[{bi}]", sinks)
                out_taint |= touts
            for oi in out_taint:
                tainted.add(eqn.outvars[oi])
            continue

        if name in ("jit", "closed_call", "core_call", "xla_call",
                    "custom_jvp_call", "custom_vjp_call", "remat", "remat2",
                    "checkpoint", "custom_vjp_call_jaxpr"):
            closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if closed is not None:
                inner = getattr(closed, "jaxpr", closed)
                sub = {iv for iv, ov in zip(inner.invars, eqn.invars)
                       if not isinstance(ov, Literal) and ov in tainted}
                touts = _walk(inner, sub, f"{path}/{name}", sinks)
                for oi in touts:
                    tainted.add(eqn.outvars[oi])
                continue

        if name == "while":
            cj = eqn.params["cond_jaxpr"]
            bj = eqn.params["body_jaxpr"]
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            carry_ops = eqn.invars[cn + bn:]
            carry_taint = {i for i, ov in enumerate(carry_ops)
                           if not isinstance(ov, Literal) and ov in tainted}
            body_const_taint = {
                i for i, ov in enumerate(eqn.invars[cn:cn + bn])
                if not isinstance(ov, Literal) and ov in tainted}
            cond_const_taint = {
                i for i, ov in enumerate(eqn.invars[:cn])
                if not isinstance(ov, Literal) and ov in tainted}
            # fixpoint over the carry: one body pass can taint new slots
            for _ in range(len(carry_ops) + 1):
                bvars = bj.jaxpr.invars
                sub = {bvars[i] for i in body_const_taint}
                sub |= {bvars[bn + i] for i in carry_taint}
                new_carry = _walk(bj.jaxpr, sub, f"{path}/while.body", sinks)
                if new_carry <= carry_taint:
                    break
                carry_taint |= new_carry
            cvars = cj.jaxpr.invars
            csub = {cvars[i] for i in cond_const_taint}
            csub |= {cvars[cn + i] for i in carry_taint}
            pred_taint = _walk(cj.jaxpr, csub, f"{path}/while.cond", sinks)
            if pred_taint:
                sinks.append(TaintSink(primitive="while",
                                       kind="while predicate", path=path,
                                       eqn_repr=str(eqn)[:200]))
            for i in carry_taint:
                tainted.add(eqn.outvars[i])
            continue

        if name == "scan":
            closed = eqn.params["jaxpr"]
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            const_taint = {i for i in range(nc) if i in hit}
            carry_taint = {i - nc for i in hit if nc <= i < nc + ncar}
            x_taint = {i - nc - ncar for i in hit if i >= nc + ncar}
            for _ in range(ncar + 1):
                ivars = closed.jaxpr.invars
                sub = {ivars[i] for i in const_taint}
                sub |= {ivars[nc + i] for i in carry_taint}
                sub |= {ivars[nc + ncar + i] for i in x_taint}
                touts = _walk(closed.jaxpr, sub, f"{path}/scan", sinks)
                new_carry = {i for i in touts if i < ncar}
                ys = {i for i in touts if i >= ncar}
                if new_carry <= carry_taint:
                    for oi in (carry_taint | ys):
                        tainted.add(eqn.outvars[oi])
                    break
                carry_taint |= new_carry
            continue

        if hit:  # default conservative rule: any in -> all out
            for ov in eqn.outvars:
                tainted.add(ov)

    return {i for i, ov in enumerate(jaxpr.outvars)
            if not isinstance(ov, Literal) and ov in tainted}


def find_taint_sinks(closed_jaxpr,
                     tainted_inputs: Sequence[int]) -> List[TaintSink]:
    """Walk a ClosedJaxpr with the given input positions tainted and return
    every control-flow/index sink the taint reaches. Purely structural --
    nothing executes."""
    jaxpr = closed_jaxpr.jaxpr
    tainted = {jaxpr.invars[i] for i in tainted_inputs}
    sinks: List[TaintSink] = []
    _walk(jaxpr, tainted, "", sinks)
    # de-dup (fixpoint iterations can record the same sink twice)
    seen, out = set(), []
    for s in sinks:
        key = (s.primitive, s.kind, s.path, s.eqn_repr)
        if key not in seen:
            seen.add(key)
            out.append(s)
    return out
