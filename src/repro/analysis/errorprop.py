"""Interval/affine-form error propagation over jaxprs (the QoI half of
the approxcost predictor, and the engine behind lint rule A007).

Each variable carries ONE abstract value: a bound on its *relative* error
(first-order affine form: the error term's coefficient, with magnitudes
normalized out).  Approximation sites inject an initial bound -- a TAF
rung's threshold residual, an iACT distance residual, a perforation mask's
dropped mass -- and the walk pushes it through every primitive to the
program outputs.  The per-primitive transfer functions are first-order
relative-error algebra with conservative headroom constants:

  * mul / div          : errors ADD (exact to first order);
  * add / sub / dot    : relative error can grow under cancellation --
                         bounded by ``CANCEL_AMP`` (model assumption:
                         operands are not pathologically cancelling);
  * transcendentals    : bounded condition number ``TRANS_AMP``;
  * select / where     : max over the data branches (a flipped predicate
                         is a control-flow discontinuity -- rule A003's
                         domain, not an error-magnitude event);
  * comparisons, argmax, iota, integer ops: exact (relative error 0);
  * anything unknown   : ``DEFAULT_AMP`` x the worst input.

Loop carries (`scan` / `while`) run to a FIXPOINT exactly like
`taint.py`'s walk: the carry's error vector is iterated through the body
until it stabilizes.  A `scan` that fails to stabilize still has a finite
trip count, so the bound closes as ``err * gain^length`` (geometric -- bad,
but bounded).  A `while` whose carry error grows per iteration has NO
static trip bound: the injected error amplifies unboundedly, which is
exactly the paper's MiniFE pathology ("locally introduced errors propagate
through subsequent iterations") made statically detectable.  Those loops
are reported as divergent -- lint rule A007.

Everything here is structural: nothing executes, bounds hold under the
documented headroom assumptions (see docs/analysis.md "Cost & error
model").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

from jax.extend.core import Literal

# Headroom constants (model assumptions, documented in docs/analysis.md).
CANCEL_AMP = 4.0    # additive cancellation headroom (add/sub/dot/reduce)
TRANS_AMP = 4.0     # transcendental condition-number headroom
DEFAULT_AMP = 4.0   # unknown-primitive fallback

_ERR_CAP = 1e30     # saturation value for divergent bounds
_MAX_FIX_ITERS = 40  # fixpoint iterations before declaring growth
_GROWTH_EPS = 1e-9   # relative growth below this counts as converged

# first-order-exact multiplicative primitives: errors add
_MUL_LIKE = {"mul", "div", "atan2", "nextafter"}
# additive / linear-combination primitives: cancellation headroom applies
_ADD_LIKE = {"add", "sub", "add_any", "complex"}
# contractions: (ra + rb) with cancellation headroom over the sum
_DOT_LIKE = {"dot_general", "conv_general_dilated"}
# bounded-condition-number nonlinearities
_TRANS = {"exp", "exp2", "expm1", "log", "log1p", "tanh", "erf", "erfc",
          "erf_inv", "rsqrt", "sqrt", "cbrt", "sin", "cos", "tan", "asin",
          "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh",
          "logistic", "pow", "integer_pow", "regularized_incomplete_beta",
          "lgamma", "digamma", "square"}
# error-preserving data movement / selection: max over float-ish inputs
_PASS = {"neg", "abs", "real", "imag", "conj", "copy", "convert_element_type",
         "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev",
         "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
         "pad", "gather", "scatter", "scatter-add", "scatter_add",
         "expand_dims", "tie_in", "stop_gradient", "reduce_sum",
         "reduce_max", "reduce_min", "cumsum", "cummax", "cummin",
         "reduce_precision", "max", "min", "clamp", "select_n", "select",
         "where", "sort", "top_k", "optimization_barrier", "copy_p",
         "device_put", "sharding_constraint", "reduce_mean", "mean",
         "transpose_p", "rem"}
# exact / discrete outputs: relative error 0 (discontinuities are A003's
# domain; discrete QoI error is the harness's MCR metric, not a bound here)
_EXACT = {"eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor", "not",
          "sign", "floor", "ceil", "round", "is_finite", "iota", "argmax",
          "argmin", "reduce_and", "reduce_or", "shift_left",
          "shift_right_logical", "shift_right_arithmetic", "population_count",
          "clz", "rng_bit_generator", "random_seed", "random_bits",
          "random_wrap", "random_fold_in", "threefry2x32", "eq_to", "nan"}


@dataclasses.dataclass(frozen=True)
class LoopReport:
    """One scan/while whose carry the injected error reaches."""

    kind: str        # "scan" | "while"
    path: str        # subjaxpr path, e.g. "jit/while.body"
    gain: float      # per-iteration amplification of the carry error
    diverges: bool   # while-loop carry with gain > 1: statically unbounded
    eqn_repr: str

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ErrorReport:
    """Propagation result: per-output relative-error bounds + loop audit."""

    out_rel: List[float]
    loops: List[LoopReport]

    @property
    def bound(self) -> float:
        """Worst output bound (inf when a divergent while is on the path)."""
        worst = max(self.out_rel, default=0.0)
        if any(lp.diverges for lp in self.loops):
            return math.inf
        return worst

    @property
    def divergent(self) -> List[LoopReport]:
        return [lp for lp in self.loops if lp.diverges]


def _in_rels(eqn, rel: Dict) -> List[float]:
    return [0.0 if isinstance(v, Literal) else rel.get(v, 0.0)
            for v in eqn.invars]


def _transfer(name: str, rels: Sequence[float]) -> float:
    """Relative-error bound of an eqn's outputs from its inputs' bounds."""
    worst = max(rels, default=0.0)
    if worst == 0.0:
        return 0.0
    if name in _EXACT:
        return 0.0
    if name in _MUL_LIKE:
        return min(sum(rels), _ERR_CAP)
    if name in _ADD_LIKE:
        return min(worst * CANCEL_AMP, _ERR_CAP)
    if name in _DOT_LIKE:
        return min(sum(rels) * CANCEL_AMP, _ERR_CAP)
    if name in _TRANS:
        return min(worst * TRANS_AMP, _ERR_CAP)
    if name in _PASS:
        return worst
    return min(worst * DEFAULT_AMP, _ERR_CAP)


def _sub_rel(inner_invars, outer_invars, rel: Dict) -> Dict:
    out: Dict = {}
    for iv, ov in zip(inner_invars, outer_invars):
        if not isinstance(ov, Literal):
            r = rel.get(ov, 0.0)
            if r:
                out[iv] = r
    return out


def _bind_out(eqn, out_rels: Sequence[float], rel: Dict) -> None:
    for ov, r in zip(eqn.outvars, out_rels):
        if r and not isinstance(ov, Literal):
            rel[ov] = max(rel.get(ov, 0.0), min(r, _ERR_CAP))


def _fixpoint(body_jaxpr, const_rels: Dict, carry0: List[float],
              x_rels: Dict, n_carry: int, carry_offset: int, path: str,
              loops: List[LoopReport]):
    """Iterate a loop body's carry error to a fixpoint.

    Returns (carry_final, other_out_rels, gain, converged): `carry_final`
    the stabilized (or last) carry bounds, `other_out_rels` the non-carry
    outputs from the final pass, `gain` the max per-iteration growth ratio
    observed on the last step, `converged` whether the carry stabilized
    within the iteration budget.
    """
    carry = list(carry0)
    gain = 1.0
    outs: List[float] = [0.0] * len(body_jaxpr.outvars)
    for _ in range(_MAX_FIX_ITERS):
        rel = dict(const_rels)
        rel.update(x_rels)
        outs = _walk_body(body_jaxpr, rel, carry, carry_offset, path, loops)
        new_carry = [max(c, o) for c, o in zip(carry, outs[:n_carry])]
        grew = [(n, c) for n, c in zip(new_carry, carry)
                if n > c * (1.0 + _GROWTH_EPS) + 1e-300]
        if not grew:
            return new_carry, outs[n_carry:], gain, True
        gain = max((n / c if c > 0 else math.inf) for n, c in grew)
        carry = new_carry
    return carry, outs[n_carry:], gain, False


def _walk_body(body_jaxpr, rel: Dict, carry: Sequence[float],
               carry_offset: int, path: str,
               loops: List[LoopReport]) -> List[float]:
    """One pass of a loop body with the carry slots bound to `carry`.
    Consts and xs were pre-bound into `rel` by the caller; the carry vars
    start at `carry_offset` (right after the body consts). Returns all
    outvar rels."""
    for i, c in enumerate(carry):
        v = body_jaxpr.invars[carry_offset + i]
        if c:
            rel[v] = c
    return _walk(body_jaxpr, rel, path, loops)


def _walk(jaxpr, rel: Dict, path: str, loops: List[LoopReport]
          ) -> List[float]:
    """Propagate relative-error bounds through one (open) jaxpr. `rel`
    maps this scope's Vars to bounds; returns per-outvar bounds."""
    rel = dict(rel)
    for eqn in jaxpr.eqns:
        rels = _in_rels(eqn, rel)
        name = eqn.primitive.name

        if name in ("cond", "switch"):
            branches = eqn.params.get("branches", ())
            outs = [0.0] * len(eqn.outvars)
            for br in branches:
                inner = br.jaxpr
                sub = _sub_rel(inner.invars, eqn.invars[1:], rel)
                bouts = _walk(inner, sub, f"{path}/cond", loops)
                outs = [max(a, b) for a, b in zip(outs, bouts)]
            _bind_out(eqn, outs, rel)
            continue

        if name in ("jit", "closed_call", "core_call", "xla_call",
                    "custom_jvp_call", "custom_vjp_call", "remat", "remat2",
                    "checkpoint", "custom_vjp_call_jaxpr"):
            closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if closed is not None:
                inner = getattr(closed, "jaxpr", closed)
                sub = _sub_rel(inner.invars, eqn.invars, rel)
                outs = _walk(inner, sub, f"{path}/{name}", loops)
                _bind_out(eqn, outs, rel)
                continue

        if name == "while":
            cj = eqn.params["cond_jaxpr"].jaxpr
            bj = eqn.params["body_jaxpr"].jaxpr
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            n_carry = len(eqn.invars) - cn - bn
            carry0 = [0.0 if isinstance(v, Literal) else rel.get(v, 0.0)
                      for v in eqn.invars[cn + bn:]]
            const_rels = _sub_rel(bj.invars[:bn], eqn.invars[cn:cn + bn],
                                  rel)
            carry, _, gain, converged = _fixpoint(
                bj, const_rels, carry0, {}, n_carry, bn,
                f"{path}/while.body", loops)
            injected = any(c > 0 for c in carry0) or bool(const_rels)
            if injected:
                diverges = not converged and gain > 1.0 + _GROWTH_EPS
                loops.append(LoopReport(
                    kind="while", path=path or "/",
                    gain=float(gain if not converged else 1.0),
                    diverges=diverges, eqn_repr=str(eqn)[:200]))
                if diverges:
                    carry = [_ERR_CAP if c > 0 else c for c in carry]
            _bind_out(eqn, carry, rel)
            continue

        if name == "scan":
            closed = eqn.params["jaxpr"]
            inner = closed.jaxpr
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            length = int(eqn.params.get("length", 1) or 1)
            carry0 = [0.0 if isinstance(v, Literal) else rel.get(v, 0.0)
                      for v in eqn.invars[nc:nc + ncar]]
            const_rels = _sub_rel(inner.invars[:nc], eqn.invars[:nc], rel)
            x_rels = _sub_rel(inner.invars[nc + ncar:],
                              eqn.invars[nc + ncar:], rel)
            carry, ys, gain, converged = _fixpoint(
                inner, const_rels, carry0, x_rels, ncar, nc,
                f"{path}/scan", loops)
            injected = (any(c > 0 for c in carry0) or bool(const_rels)
                        or bool(x_rels))
            if injected and not converged:
                # finite trip count: geometric but bounded, err * gain^L
                grow = min(gain ** max(length - _MAX_FIX_ITERS, 0), _ERR_CAP)
                carry = [min(c * grow, _ERR_CAP) for c in carry]
                ys = [min(y * grow, _ERR_CAP) for y in ys]
                loops.append(LoopReport(
                    kind="scan", path=path or "/", gain=float(gain),
                    diverges=False, eqn_repr=str(eqn)[:200]))
            _bind_out(eqn, list(carry) + list(ys), rel)
            continue

        out = _transfer(name, rels)
        _bind_out(eqn, [out] * len(eqn.outvars), rel)

    return [0.0 if isinstance(ov, Literal) else rel.get(ov, 0.0)
            for ov in jaxpr.outvars]


def propagate(closed_jaxpr, inject: Dict[int, float]) -> ErrorReport:
    """Propagate injected relative-error bounds through a ClosedJaxpr.

    `inject` maps input POSITIONS to relative-error bounds (the
    approximation-site residuals).  Returns per-output bounds plus a
    report of every loop the error flowed through -- `while` loops whose
    carry amplifies per iteration are flagged divergent (A007). Purely
    structural: nothing executes.
    """
    jaxpr = closed_jaxpr.jaxpr
    rel: Dict = {}
    for pos, r in inject.items():
        if r:
            rel[jaxpr.invars[pos]] = float(r)
    loops: List[LoopReport] = []
    outs = _walk(jaxpr, rel, "", loops)
    # de-dup (fixpoint iterations can record the same loop twice)
    seen, uniq = set(), []
    for lp in loops:
        key = (lp.kind, lp.path, lp.eqn_repr, lp.diverges)
        if key not in seen:
            seen.add(key)
            uniq.append(lp)
    return ErrorReport(out_rel=outs, loops=uniq)


def amplification(fn, example_args, inject_positions: Sequence[int],
                  rel: float = 1.0) -> ErrorReport:
    """Trace `fn(*example_args)` and propagate a `rel` bound injected at
    the given argument positions. Convenience wrapper used by the cost
    model's site->QoI amplification factor and the A007 targets."""
    import jax
    closed = jax.make_jaxpr(fn)(*example_args)
    return propagate(closed, {p: rel for p in inject_positions})


def find_divergent_carries(closed_jaxpr,
                           inject_positions: Sequence[int]
                           ) -> List[LoopReport]:
    """A007 helper: while-loop carries that amplify an error injected at
    the given input positions without a static bound."""
    rep = propagate(closed_jaxpr, {p: 1.0 for p in inject_positions})
    return rep.divergent
