"""Static program analysis over traced jaxprs (ROADMAP item: soundness).

Every legality decision the pass pipeline makes about user callables used
to be a *runtime sampling probe* — evaluate the gather/apply on a fixed
random batch and compare numerics.  Probes are documented (here, on each
probe function) as evidence-not-proof: a function that misbehaves only on
values outside the probe batch slips through.  This module closes that
hole for everything JAX can trace: :func:`analyze_program` stages the
user's ``gather``/``apply`` through ``jax.make_jaxpr`` on abstract
``(8,)`` avals and decides each property by *walking the primitives*
instead of sampling values:

* **gather module matching** — canonical-jaxpr signature equality against
  the pre-built menu (``kernels.ref.GATHER_OPS``), replacing numeric
  coincidence on a 16-element batch;
* **weight-use** — backward liveness of the weight argument, replacing
  the hardcoded ``WEIGHT_FREE_GATHERS`` name list (any user gather whose
  jaxpr drops the weight gets the one-gather-per-slot pull sweep);
* **elementwise-ness** — no cross-lane primitive touches the vertex axis;
* **identity-fixpoint** / **identity-absorption** — symbolic evaluation
  of the jaxpr at the reduce identity over a small abstract domain
  (concrete arrays, symbolic-input-plus-offset-interval, bounded
  intervals);
* **monotonicity under min/max reduces** — termination evidence: a
  clamp-shaped apply plus a sign-bounded gather offset proves vertex
  values move one way only;
* **dtype/overflow bounds** — integer folds are re-computed in int64, so
  ``gather(init)`` silently wrapping int32 becomes a typed diagnostic.

Every property records **provenance**: ``'static'`` (decided from the
jaxpr — authoritative), ``'probed'`` (the jaxpr was opaque or the
abstract domain too coarse; the legacy sampling probe decided, and an
``A001`` diagnostic says so), or ``'declined'`` (neither worked; the
conservative verdict).  Where both the static analysis and a probe reach
a verdict they are cross-checked — disagreement is a soundness alarm
(``A002``) and the conservative verdict wins.

The sampling probes themselves (:func:`classify_gather`,
:func:`apply_preserves_identity`, :func:`gather_absorbs_identity`,
:func:`apply_is_elementwise`) moved here from ``core/passes.py`` — they
are now the *fallback tier*, re-exported from :mod:`repro.core.passes`
for back-compat.

:func:`verify_ir` is the second half of the module: an LLVM-verifier
style structural check over :class:`~repro.core.ir.SuperstepIR`
(op multiplicity/ordering, reduce/dtype consistency, direction and
fused-binding preconditions, exchange-plane agreement) that
``PassPipeline.run(..., verify=True)`` executes between every pass pair,
so a buggy transform fails at the pass boundary with a typed ``V*``
diagnostic instead of as wrong numerics three layers down.

Analysis results are cached per program object (:data:`_ANALYSIS_CACHE`),
so repeat translations pay nothing — the probes used to re-run three or
four times per cold translate; now even the first translate runs each at
most once, as a cross-check.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ref import GATHER_OPS, WEIGHT_FREE_GATHERS, gather_msg
from .diagnostics import Diagnostic
from .dsl import VertexProgram, reduce_identity

__all__ = [
    "PropertyFact",
    "ProgramAnalysis",
    "analyze_program",
    "analysis_cache_clear",
    "verify_ir",
    "classify_gather",
    "apply_preserves_identity",
    "gather_absorbs_identity",
    "apply_is_elementwise",
]

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var

# Number of abstract lanes traced; any small n > 1 works (the analysis is
# shape-polymorphic in spirit — avals only pin dtype and rank).
_N = 8

# Finite stand-in for "any finite magnitude" in interval bounds.
_FIN = float(np.finfo(np.float64).max)

# Reduce ops that commute and have a two-sided identity (mirrors
# passes.COMMUTATIVE_REDUCES; kept local to avoid an import cycle).
_COMMUTATIVE_REDUCES = ("add", "min", "max")

_PROVENANCES = ("static", "probed", "declined")


# ---------------------------------------------------------------------------
# Result types
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PropertyFact:
    """One analyzed property: its verdict and how it was reached.

    ``provenance`` is ``'static'`` (decided from the traced jaxpr),
    ``'probed'`` (the legacy sampling probe decided — the jaxpr was
    opaque or the abstract domain too coarse), or ``'declined'``
    (neither analysis nor probe could run; ``value`` is the conservative
    default).  ``detail`` is a short human-readable justification.
    """

    value: object
    provenance: str
    detail: str = ""

    def __post_init__(self):
        if self.provenance not in _PROVENANCES:
            raise ValueError(f"unknown provenance: {self.provenance!r}")


@dataclasses.dataclass(frozen=True)
class ProgramAnalysis:
    """All analyzed facts for one :class:`~repro.core.dsl.VertexProgram`.

    The pass pipeline consumes these instead of re-running probes:
    ``gather_module`` feeds gather classification, ``weight_use`` the
    pull-sweep table mode, ``elementwise`` superstep fusion,
    ``identity_fixpoint`` the push layout / touched-mask elision,
    ``identity_absorbing`` the dense masked sweep, and ``monotone`` is
    termination evidence for ``'changed'``-frontier programs.
    ``diagnostics`` carries the program-level findings (overflow,
    probe/static disagreement, absorbing init, missing termination
    evidence).
    """

    gather_module: PropertyFact      # str | None
    weight_use: PropertyFact         # bool
    elementwise: PropertyFact        # bool
    identity_fixpoint: PropertyFact  # bool
    identity_absorbing: PropertyFact # bool
    monotone: PropertyFact           # bool
    diagnostics: tuple = ()

    def summary(self) -> dict:
        """``{property: (value, provenance)}`` — what the pins assert."""
        return {
            f.name: (getattr(self, f.name).value,
                     getattr(self, f.name).provenance)
            for f in dataclasses.fields(self)
            if f.name != "diagnostics"
        }


# ---------------------------------------------------------------------------
# Tracing and canonical signatures
# ---------------------------------------------------------------------------


def _trace(fn: Callable, *avals):
    """``jax.make_jaxpr`` on abstract avals; ``None`` if untraceable."""
    try:
        return jax.make_jaxpr(fn)(*avals)
    except Exception:
        return None


def _gather_avals(dtype):
    f = jax.ShapeDtypeStruct((_N,), jnp.dtype(dtype))
    d = jax.ShapeDtypeStruct((_N,), jnp.int32)
    return f, f, d                       # (values, weights, degrees)


def _apply_avals(dtype):
    f = jax.ShapeDtypeStruct((_N,), jnp.dtype(dtype))
    return f, f                          # (old, reduced)


def _const_repr(val) -> str:
    a = np.asarray(val)
    if a.size <= 16:
        return f"c({a.dtype}:{a.shape}:{a.tolist()})"
    return f"c({a.dtype}:{a.shape}:#{hash(a.tobytes())})"


def _param_repr(val) -> str:
    if isinstance(val, ClosedJaxpr):
        return "{" + _signature(val) + "}"
    if isinstance(val, Jaxpr):
        return "{" + _jaxpr_signature(val, {}) + "}"
    if isinstance(val, (tuple, list)):
        return "(" + ",".join(_param_repr(v) for v in val) + ")"
    if callable(val) and not isinstance(val, type):
        # function-valued params (custom_jvp rules, pjit names) are
        # identity-unstable across traces; the jaxpr-valued params carry
        # the real content, so collapse these to their type
        return f"<{type(val).__name__}>"
    return repr(val)


# Two-operand primitives where operand order is semantically irrelevant —
# their operand reprs are sorted so `a + b` and `b + a` match.
_COMMUTATIVE_PRIMS = frozenset(
    ["add", "mul", "min", "max", "eq", "ne", "and", "or", "xor"])


def _jaxpr_signature(jaxpr, names: dict) -> str:
    """Canonical text for one (open) jaxpr under sequential var renaming."""

    def vname(v):
        if isinstance(v, Literal):
            return _const_repr(v.val)
        if v not in names:
            names[v] = f"v{len(names)}"
        return names[v]

    parts = []
    for v in jaxpr.constvars:
        parts.append(f"const {vname(v)}:{v.aval.str_short()}")
    parts.append("in " + ",".join(
        f"{vname(v)}:{v.aval.str_short()}" for v in jaxpr.invars))
    for eqn in jaxpr.eqns:
        ins = [vname(v) for v in eqn.invars]
        if eqn.primitive.name in _COMMUTATIVE_PRIMS and len(ins) == 2:
            ins = sorted(ins)
        params = ",".join(f"{k}={_param_repr(v)}"
                          for k, v in sorted(eqn.params.items()))
        outs = ",".join(vname(v) for v in eqn.outvars)
        parts.append(f"{outs} = {eqn.primitive.name}[{params}] "
                     + ",".join(ins))
    parts.append("out " + ",".join(vname(v) for v in jaxpr.outvars))
    return "; ".join(parts)


def _signature(closed) -> str:
    """Canonical signature of a ``ClosedJaxpr`` (consts folded into text)."""
    names: dict = {}
    sig = _jaxpr_signature(closed.jaxpr, names)
    consts = ",".join(_const_repr(c) for c in closed.consts)
    return f"[{consts}] {sig}" if consts else sig


_MENU_SIG_CACHE: dict = {}


def _menu_signatures(dtype) -> dict:
    """``{canonical signature: module name}`` for the menu, per dtype."""
    key = str(jnp.dtype(dtype))
    if key not in _MENU_SIG_CACHE:
        sigs = {}
        for name in GATHER_OPS:
            closed = _trace(partial(gather_msg, name), *_gather_avals(dtype))
            if closed is not None:
                sigs[_signature(closed)] = name
        _MENU_SIG_CACHE[key] = sigs
    return _MENU_SIG_CACHE[key]


# ---------------------------------------------------------------------------
# Liveness (which invars reach the outputs)
# ---------------------------------------------------------------------------


def _live_eqns(jaxpr):
    """Equations whose outputs (transitively) reach the jaxpr outvars."""
    live_vars = {v for v in jaxpr.outvars if isinstance(v, Var)}
    live = []
    for eqn in reversed(jaxpr.eqns):
        if any(v in live_vars for v in eqn.outvars):
            live.append(eqn)
            for v in eqn.invars:
                if isinstance(v, Var):
                    live_vars.add(v)
    live.reverse()
    return live, live_vars


def _uses_invar(closed, index: int) -> bool:
    """Backward liveness: does ``invars[index]`` reach the outputs?"""
    jaxpr = closed.jaxpr
    _, live_vars = _live_eqns(jaxpr)
    return jaxpr.invars[index] in live_vars


# ---------------------------------------------------------------------------
# Elementwise-ness (cross-lane primitive walk)
# ---------------------------------------------------------------------------

# Primitives that act independently per lane of the vertex axis (output
# lane i depends only on operand lanes i, the same way at every i).  The
# property must match the probe's permutation test: position-*dependent*
# ops (iota — "which lane am I?") are excluded even though they read no
# other lane, because the fused superstep kernels tile the vertex axis
# and only a position-independent apply commutes with retiling.
_LANEWISE = frozenset([
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg", "sign",
    "floor", "ceil", "round", "abs", "exp", "exp2", "log", "log1p", "expm1",
    "sqrt", "rsqrt", "cbrt", "sin", "cos", "tan", "asin", "acos", "atan",
    "atan2", "sinh", "cosh", "tanh", "logistic", "erf", "erfc", "erf_inv",
    "min", "max", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "population_count",
    "clz", "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
    "convert_element_type", "bitcast_convert_type", "copy", "stop_gradient",
    "is_finite", "nextafter", "square", "real", "imag",
])

# Primitives that definitely mix lanes, change the lane structure, or
# read the lane *position* (iota) — any live occurrence refutes
# elementwise-ness outright.
_CROSS_LANE = frozenset([
    "iota",
    "reduce_sum", "reduce_prod", "reduce_min", "reduce_max", "reduce_and",
    "reduce_or", "reduce_xor", "reduce_precision", "argmax", "argmin",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp", "sort",
    "gather", "scatter", "scatter_add", "scatter_min", "scatter_max",
    "scatter_mul", "dynamic_slice", "dynamic_update_slice", "slice", "rev",
    "concatenate", "pad", "dot_general", "conv_general_dilated", "fft",
    "while", "scan", "all_gather", "all_to_all", "ppermute", "psum",
    "pmax", "pmin",
])


def _sub_jaxprs(eqn):
    """Jaxpr-valued params of a call-like equation (pjit, custom_jvp, …)."""
    subs = []
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            subs.append(v.jaxpr)
        elif isinstance(v, Jaxpr):
            subs.append(v)
    return subs


def _walk_elementwise(jaxpr) -> bool | None:
    """``True`` all live prims lanewise, ``False`` a cross-lane prim is
    live, ``None`` an unclassified prim appeared (undecided)."""
    live, _ = _live_eqns(jaxpr)
    undecided = False
    for eqn in live:
        name = eqn.primitive.name
        if name in _CROSS_LANE:
            return False
        if name in _LANEWISE:
            continue
        if name == "broadcast_in_dim":
            # scalar → vector (a constant per lane) is lanewise; anything
            # reshaping an existing vector axis is not provably so
            (op,) = eqn.invars
            if np.ndim(op.aval) == 0 or op.aval.shape == eqn.outvars[0].aval.shape:
                continue
            undecided = True
            continue
        subs = _sub_jaxprs(eqn)
        if subs:
            verdicts = [_walk_elementwise(s) for s in subs]
            if any(v is False for v in verdicts):
                return False
            if any(v is None for v in verdicts):
                undecided = True
            continue
        undecided = True                 # unknown primitive: punt to probe
    return None if undecided else True


def _static_elementwise(closed) -> bool | None:
    jaxpr = closed.jaxpr
    out_shapes = [tuple(v.aval.shape) for v in jaxpr.outvars]
    if out_shapes != [(_N,)]:
        return False                     # shape change is never elementwise
    return _walk_elementwise(jaxpr)


# ---------------------------------------------------------------------------
# Abstract interpreter (symbolic evaluation at the reduce identity)
# ---------------------------------------------------------------------------


class _Top:
    """Unknown value (abstract ⊤)."""

    def __repr__(self):
        return "⊤"


_TOP = _Top()


@dataclasses.dataclass(frozen=True)
class _Aff:
    """``invar[var] + δ`` with the additive offset δ ∈ ``[lo, hi]``."""

    var: int
    lo: float
    hi: float


@dataclasses.dataclass(frozen=True)
class _Rng:
    """Unknown value bounded to ``[lo, hi]`` (per lane)."""

    lo: float
    hi: float


class _EvalState:
    """Per-evaluation flags: opacity and detected integer wraparound."""

    def __init__(self):
        self.opaque = False              # hit a primitive we cannot model
        self.wrapped = False             # an integer fold over/underflowed


def _is_conc(x) -> bool:
    return isinstance(x, np.ndarray)


def _uniform_scalar(a: np.ndarray):
    """The scalar ``c`` if every element of ``a`` equals ``c``, else None."""
    flat = a.reshape(-1)
    if flat.size == 0:
        return None
    c = flat[0]
    with np.errstate(invalid="ignore"):
        same = bool(np.all(flat == c))
    return c if same else None


def _conc_bounds(a: np.ndarray) -> tuple[float, float]:
    return float(np.min(a)), float(np.max(a))


def _bounds(x) -> tuple[float, float] | None:
    """Interval bounds of a non-symbolic abstract value, if known."""
    if _is_conc(x):
        return _conc_bounds(x)
    if isinstance(x, _Rng):
        return x.lo, x.hi
    return None


def _fold(eqn, args, state: _EvalState):
    """Concretely evaluate one equation; flags integer wraparound."""
    try:
        out = eqn.primitive.bind(*[jnp.asarray(a) for a in args],
                                 **eqn.params)
    except Exception:
        state.opaque = True
        return [_TOP] * len(eqn.outvars)
    outs = list(out) if eqn.primitive.multiple_results else [out]
    if eqn.primitive.name in ("add", "sub", "mul") and args:
        dt = np.asarray(outs[0]).dtype
        if np.issubdtype(dt, np.integer) and dt.itemsize < 8:
            wide = [np.asarray(a, np.int64) for a in args]
            ref = {"add": np.add, "sub": np.subtract,
                   "mul": np.multiply}[eqn.primitive.name](*wide)
            if not np.array_equal(np.asarray(outs[0], np.int64), ref):
                state.wrapped = True
    return [np.asarray(o) for o in outs]


def _add_rule(a, b, sign: int, state: _EvalState, int_dtype):
    """Abstract ``a + sign*b`` (sign=-1 for sub)."""
    if isinstance(a, _Aff) and (bnds := _bounds(b)) is not None:
        lo, hi = bnds
        if sign < 0:
            lo, hi = -hi, -lo
        return _Aff(a.var, a.lo + lo, a.hi + hi)
    if isinstance(b, _Aff) and sign > 0 and (bnds := _bounds(a)) is not None:
        return _Aff(b.var, b.lo + bnds[0], b.hi + bnds[1])
    ba, bb = _bounds(a), _bounds(b)
    if ba is not None and bb is not None:
        # ±inf + finite stays ±inf; inf - inf is unknowable
        lo = ba[0] + sign * (bb[1] if sign < 0 else bb[0])
        hi = ba[1] + sign * (bb[0] if sign < 0 else bb[1])
        if np.isnan(lo) or np.isnan(hi):
            return _TOP
        if int_dtype is not None:
            info = np.iinfo(int_dtype)
            if lo < info.min or hi > info.max:
                state.wrapped = True
                return _TOP
        # a concrete ±inf array shifted by a finite interval is unchanged
        if _is_conc(a) and np.all(~np.isfinite(a)) and np.all(np.isfinite([bb[0], bb[1]])):
            return a
        if _is_conc(b) and sign > 0 and np.all(~np.isfinite(b)) \
                and np.all(np.isfinite([ba[0], ba[1]])):
            return b
        return _Rng(lo, hi)
    return _TOP


def _mul_rule(a, b, state: _EvalState, int_dtype):
    ba, bb = _bounds(a), _bounds(b)
    if ba is None or bb is None:
        # x * 1 (exactly) passes an _Aff through
        for sym, conc in ((a, b), (b, a)):
            if isinstance(sym, _Aff) and _is_conc(conc) \
                    and _uniform_scalar(conc) == 1:
                return sym
        return _TOP
    # all-zero concrete × any finite interval is zero
    for z, other, obnds in ((a, b, bb), (b, a, ba)):
        if _is_conc(z) and not np.any(z) and np.isfinite(obnds[0]) \
                and np.isfinite(obnds[1]):
            return z
    cands = [ba[0] * bb[0], ba[0] * bb[1], ba[1] * bb[0], ba[1] * bb[1]]
    if any(np.isnan(c) for c in cands):
        return _TOP
    lo, hi = min(cands), max(cands)
    if int_dtype is not None:
        info = np.iinfo(int_dtype)
        if lo < info.min or hi > info.max:
            state.wrapped = True
            return _TOP
    return _Rng(lo, hi)


def _div_rule(a, b):
    ba, bb = _bounds(a), _bounds(b)
    if ba is None or bb is None:
        return _TOP
    if _is_conc(a) and not np.any(a) and bb[0] > 0:
        return a                         # 0 / positive = 0
    if bb[0] > 0 or bb[1] < 0:           # denominator excludes zero
        cands = [ba[0] / bb[0], ba[0] / bb[1], ba[1] / bb[0], ba[1] / bb[1]]
        if any(np.isnan(c) for c in cands):
            return _TOP
        return _Rng(min(cands), max(cands))
    return _TOP


def _minmax_rule(name: str, a, b, out_dtype):
    pick = min if name == "min" else max
    # x clamped against the reduce identity of its own extreme is x
    for sym, conc in ((a, b), (b, a)):
        if isinstance(sym, _Aff) and _is_conc(conc):
            c = _uniform_scalar(conc)
            if c is not None:
                if np.issubdtype(out_dtype, np.floating):
                    neutral = np.inf if name == "min" else -np.inf
                else:
                    info = np.iinfo(out_dtype)
                    neutral = info.max if name == "min" else info.min
                if c == neutral:
                    return sym
            return _TOP                  # a real clamp of an unknown base
    if isinstance(a, _Aff) and isinstance(b, _Aff) and a.var == b.var:
        return _Aff(a.var, pick(a.lo, b.lo), pick(a.hi, b.hi))
    ba, bb = _bounds(a), _bounds(b)
    if ba is not None and bb is not None:
        return _Rng(pick(ba[0], bb[0]), pick(ba[1], bb[1]))
    return _TOP


def _eval_jaxpr(jaxpr, consts, args, state: _EvalState):
    """Abstractly evaluate ``jaxpr`` over the Conc/_Aff/_Rng/⊤ domain."""
    env: dict = {}

    def read(v):
        if isinstance(v, Literal):
            return np.asarray(v.val)
        return env[v]

    def write(v, val):
        env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, np.asarray(c))
    for v, a in zip(jaxpr.invars, args):
        write(v, a)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        out_aval = eqn.outvars[0].aval
        out_dtype = np.dtype(out_aval.dtype) if hasattr(out_aval, "dtype") \
            else None
        int_dtype = out_dtype if out_dtype is not None \
            and np.issubdtype(out_dtype, np.integer) \
            and out_dtype.itemsize < 8 else None

        if all(_is_conc(i) for i in ins) and not _sub_jaxprs(eqn):
            outs = _fold(eqn, ins, state)
            for v, o in zip(eqn.outvars, outs):
                write(v, o)
            continue

        subs = _sub_jaxprs(eqn)
        if subs and len(subs) >= 1 and name in (
                "pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
                "custom_vjp_call_jaxpr", "remat", "remat2", "checkpoint"):
            sub = subs[0]
            sub_consts = []
            for v in eqn.params.values():
                if isinstance(v, ClosedJaxpr):
                    sub_consts = v.consts
                    break
            outs = _eval_jaxpr(sub, sub_consts, ins, state)
            for v, o in zip(eqn.outvars, outs):
                write(v, o)
            continue

        if name in ("add", "sub"):
            out = _add_rule(ins[0], ins[1], 1 if name == "add" else -1,
                            state, int_dtype)
        elif name == "mul":
            out = _mul_rule(ins[0], ins[1], state, int_dtype)
        elif name == "div":
            out = _div_rule(ins[0], ins[1])
        elif name in ("min", "max"):
            out = _minmax_rule(name, ins[0], ins[1], out_dtype)
        elif name == "neg":
            b = _bounds(ins[0])
            out = _Rng(-b[1], -b[0]) if b is not None \
                and not _is_conc(ins[0]) else _TOP
        elif name == "convert_element_type":
            x = ins[0]
            src = eqn.invars[0].aval.dtype
            if isinstance(x, _Aff):
                out = x if np.dtype(src) == out_dtype else _TOP
            elif isinstance(x, _Rng):
                out = x                  # bounds survive a value cast
            else:
                out = _TOP
        elif name == "broadcast_in_dim":
            x = ins[0]
            out = x if isinstance(x, (_Rng, _Aff)) else _TOP
        elif name in ("copy", "stop_gradient"):
            out = ins[0]
        elif name == "select_n":
            branches = ins[1:]
            same = all(isinstance(b, type(branches[0])) and b == branches[0]
                       if not _is_conc(b) else False for b in branches[1:])
            if len(branches) >= 2 and all(_is_conc(b) for b in branches) \
                    and all(np.array_equal(b, branches[0])
                            for b in branches[1:]):
                out = branches[0]
            elif same and not _is_conc(branches[0]):
                out = branches[0]
            else:
                out = _TOP
        elif name in ("eq", "ne", "lt", "le", "gt", "ge"):
            out = _Rng(0.0, 1.0)         # unknown predicate, bounded bool
        else:
            state.opaque = True
            out = _TOP
        if eqn.primitive.multiple_results:
            for v in eqn.outvars:
                write(v, _TOP)
        else:
            write(eqn.outvars[0], out)

    return [read(v) for v in jaxpr.outvars]


def _eval_closed(closed, args, state: _EvalState):
    outs = _eval_jaxpr(closed.jaxpr, closed.consts, args, state)
    return outs[0] if len(outs) == 1 else _TOP


# ---------------------------------------------------------------------------
# Sampling probes (fallback tier — moved here from core/passes.py)
# ---------------------------------------------------------------------------


def classify_gather(gather: Callable, dtype) -> str | None:
    """Match a gather callable against the menu by *sampling probe*.

    The paper's "eliminate complex grammatical and semantic analysis":
    probe the gather on a fixed random batch and compare against every
    menu entry (``kernels.ref.GATHER_OPS``).  Returns the matched module
    name, or ``None`` for the general path.

    Since the static analyzer landed, this probe is the *fallback tier*
    (opaque callables) and a cross-check on the canonical-jaxpr signature
    match — numeric coincidence on the batch no longer decides the fast
    path on its own (see :func:`analyze_program`).
    """
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.uniform(1, 8, (16,)), dtype)
    w = jnp.asarray(rng.uniform(1, 8, (16,)),
                    dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.float32)
    d = jnp.asarray(rng.integers(1, 9, (16,)), jnp.int32)
    try:
        got = np.asarray(gather(v, w.astype(v.dtype), d))
    except Exception:
        return None
    for name in GATHER_OPS:
        try:
            want = np.asarray(gather_msg(name, v, w.astype(v.dtype), d))
        except Exception:
            continue
        if got.shape == want.shape and np.allclose(got, want, rtol=1e-5, atol=1e-5):
            return name
    return None


def apply_preserves_identity(apply: Callable, reduce: str, dtype) -> bool:
    """Probe whether ``apply(x, identity) == x`` bit-exactly.

    The sampling fallback/cross-check for the analyzer's symbolic
    identity-fixpoint evaluation: evaluate the user's apply on a fixed
    batch (random values plus the edge cases — zero, the identity
    itself, extreme magnitudes) against the folded reduce identity, and
    require *exact* equality.  When it holds, an untouched vertex is a
    fixpoint of the superstep, so the push engine may apply the reduced
    table everywhere and skip scattering a separate touched mask — half
    the scatter traffic, and the compacted kernel's combine stays a
    single segment reduce.  ``jnp.minimum``/``maximum`` applies
    (BFS/SSSP/WCC) and integer ``old + s`` all pass; overwrite- or
    offset-style applies fail, and the fusion pass binds the
    chunk-streamed ``'coo_chunks'`` push layout (which keeps the touched
    mask) instead of the compacted engine.

    This is evidence, not proof: an adversarial apply that misbehaves
    only on values outside the probe batch would pass — which is exactly
    why :func:`analyze_program` decides statically whenever the apply is
    traceable, and treats probe/static disagreement as a soundness alarm
    (``A002``).  Probes use fixed seeds, so the decision is at least
    deterministic.
    """
    ident = reduce_identity(reduce, dtype)
    rng = np.random.default_rng(0)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        info = np.finfo(np.dtype(dtype))
        probes = np.concatenate([
            rng.uniform(-8, 8, 13), [0.0, info.max / 2, -info.max / 2]])
    else:
        info = np.iinfo(np.dtype(dtype))
        probes = np.concatenate([
            rng.integers(-8, 8, 13), [0, info.max - 1, info.min + 1]])
    x = jnp.asarray(probes, dtype)
    try:
        got = np.asarray(apply(x, jnp.full_like(x, ident)))
    except Exception:
        return False
    return got.shape == x.shape and np.array_equal(got, np.asarray(x))


def gather_absorbs_identity(gather: Callable, reduce: str, dtype) -> bool:
    """Probe whether the reduce identity absorbs through the gather:
    ``gather(identity, w, d) == identity`` for any weight/degree.

    When it holds, the dense sweep for a *weight-dependent* gather can
    pre-mask the vertex-value table once (inactive/PAD sources hold the
    identity) and evaluate the gather per edge without a separate
    frontier gather — e.g. SSSP's ``dist + w``: ``inf + w == inf``.
    Integer identities generally fail (``INT_MAX + 1`` wraps), keeping
    the classic masked form.  Fallback/cross-check tier for the
    analyzer's symbolic evaluation at the identity (fixed seeds,
    evidence not proof — see :func:`analyze_program`).
    """
    ident = reduce_identity(reduce, dtype)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.uniform(-8, 8, (16,)),
                    dtype if jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
                    else jnp.float32)
    d = jnp.asarray(rng.integers(1, 9, (16,)), jnp.int32)
    x = jnp.full((16,), ident, dtype)
    try:
        got = np.asarray(gather(x, w.astype(x.dtype), d))
    except Exception:
        return False
    return got.shape == (16,) and np.array_equal(
        got, np.asarray(jnp.full((16,), ident, dtype)), equal_nan=True)


def apply_is_elementwise(apply: Callable, dtype) -> bool:
    """Probe whether ``apply`` is elementwise: output ``i`` depends only on
    ``(old[i], reduced[i])``.

    The legality condition for fusing the whole superstep into one stage
    (``SuperstepFusionPass``): an elementwise apply commutes with the
    sweep's row→vertex data movement, so the reduced values can flow into
    the apply and the change mask without a materialized full-table
    intermediate between stages.  Probed checks:

    * shape preservation — ``apply(x, r).shape == x.shape``;
    * per-element agreement — evaluating element-by-element reproduces
      the batch result bit-exactly;
    * locality — perturbing one input slot changes no *other* output slot.

    Every DSL template apply (``jnp.minimum``, damped sums, overwrite)
    passes; reductions-over-the-table style applies (e.g. a normalizing
    ``old / s.sum()``) fail and keep the unfused three-stage emission.
    Fallback/cross-check tier for the analyzer's cross-lane primitive
    walk — an apply that is non-elementwise only outside the probe batch
    slips past the probe but not the jaxpr walk (fixed seeds keep the
    probe deterministic).
    """
    rng = np.random.default_rng(1)
    n = 8
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        xs = rng.uniform(-8, 8, (2, n))
    else:
        xs = rng.integers(-8, 8, (2, n))
    x = jnp.asarray(xs[0], dtype)
    r = jnp.asarray(xs[1], dtype)
    try:
        full = np.asarray(apply(x, r))
        if full.shape != (n,):
            return False
        per = np.stack([np.asarray(apply(x[i:i + 1], r[i:i + 1]))[0]
                        for i in range(n)])
        if not np.array_equal(full, per, equal_nan=True):
            return False
        for k in (0, n - 1):
            x2 = x.at[k].add(jnp.asarray(1, dtype))
            r2 = r.at[k].add(jnp.asarray(1, dtype))
            out2 = np.asarray(apply(x2, r2))
            others = np.arange(n) != k
            if not np.array_equal(full[others], out2[others], equal_nan=True):
                return False
    except Exception:
        return False
    return True


# ---------------------------------------------------------------------------
# Property deciders (static first, probe as fallback and cross-check)
# ---------------------------------------------------------------------------


def _identity_scalar(program: VertexProgram):
    return np.asarray(reduce_identity(program.reduce, program.value_dtype))


def _decide_module(program, gather_jaxpr, diags) -> PropertyFact:
    probe = classify_gather(program.gather, program.value_dtype)
    if gather_jaxpr is None:
        if probe is None:
            return PropertyFact(None, "declined",
                                "gather untraceable and probe matched nothing")
        diags.append(Diagnostic(
            "A001", "info", "gather",
            f"module match {probe!r} rests on a 16-element sampling probe "
            "(gather is opaque to jaxpr tracing)",
            "prefer jax-traceable gathers so the match is proved, "
            "not sampled"))
        return PropertyFact(probe, "probed",
                            f"probe matched {probe!r} (jaxpr opaque)")
    static = _menu_signatures(program.value_dtype).get(_signature(gather_jaxpr))
    if static != probe and probe is not None and static is None:
        diags.append(Diagnostic(
            "A002", "warning", "gather",
            f"sampling probe matched module {probe!r} but the gather's "
            "jaxpr is not that module — numeric coincidence on the probe "
            "batch; the general path is used",
            "if the gather really is the menu op, write it in the menu "
            "form so the signatures align"))
    detail = (f"canonical jaxpr signature matched {static!r}" if static
              else "canonical jaxpr signature matched no menu module")
    return PropertyFact(static, "static", detail)


def _decide_weight_use(program, gather_jaxpr) -> PropertyFact:
    if gather_jaxpr is not None:
        used = _uses_invar(gather_jaxpr, 1)
        return PropertyFact(
            used, "static",
            "weight argument is " + ("live in" if used else "dead in")
            + " the gather jaxpr")
    probe = classify_gather(program.gather, program.value_dtype)
    if probe is not None:
        return PropertyFact(probe not in WEIGHT_FREE_GATHERS, "probed",
                            f"inferred from probe-matched module {probe!r}")
    return PropertyFact(True, "declined",
                        "gather untraceable; conservatively weight-using")


def _decide_elementwise(program, apply_jaxpr, diags) -> PropertyFact:
    probe_ok = None

    def probe():
        nonlocal probe_ok
        if probe_ok is None:
            probe_ok = apply_is_elementwise(program.apply,
                                            program.value_dtype)
        return probe_ok

    if apply_jaxpr is None:
        if _callable_probe_usable(program.apply):
            diags.append(Diagnostic(
                "A001", "info", "apply",
                "elementwise-ness decided by sampling probe only (apply is "
                "opaque to jaxpr tracing)",
                "prefer jax-traceable applies so fusion legality is proved"))
            return PropertyFact(probe(), "probed", "apply jaxpr opaque")
        return PropertyFact(False, "declined",
                            "apply untraceable; fusion declined")
    static = _static_elementwise(apply_jaxpr)
    if static is None:
        diags.append(Diagnostic(
            "A001", "info", "apply",
            "elementwise-ness decided by sampling probe (an unclassified "
            "primitive appears in the apply jaxpr)", ""))
        return PropertyFact(probe(), "probed",
                            "unclassified primitive in apply jaxpr")
    if static is False and probe():
        diags.append(Diagnostic(
            "A002", "warning", "apply",
            "sampling probe calls the apply elementwise but its jaxpr "
            "contains a cross-lane primitive — the probe batch missed the "
            "mixing; fusion is declined",
            ""))
    return PropertyFact(
        static, "static",
        "live apply primitives are all lanewise" if static
        else "a cross-lane primitive is live in the apply jaxpr")


def _callable_probe_usable(fn) -> bool:
    return callable(fn)


def _decide_fixpoint(program, apply_jaxpr, diags) -> PropertyFact:
    probe_val = apply_preserves_identity(program.apply, program.reduce,
                                         program.value_dtype)
    if apply_jaxpr is None:
        diags.append(Diagnostic(
            "A001", "info", "apply",
            "identity-fixpoint decided by sampling probe only (apply is "
            "opaque to jaxpr tracing)", ""))
        return PropertyFact(probe_val, "probed", "apply jaxpr opaque")
    ident = _identity_scalar(program)
    state = _EvalState()
    x = _Aff(0, 0.0, 0.0)
    s = np.full((_N,), ident, ident.dtype)
    out = _eval_closed(apply_jaxpr, [x, s], state)
    if isinstance(out, _Aff) and out.var == 0 and out.lo == 0 == out.hi:
        static, detail = True, "apply(x, identity) reduces to x symbolically"
    elif isinstance(out, _Aff) and (out.lo > 0 or out.hi < 0):
        static, detail = False, "apply(x, identity) is x plus a nonzero offset"
    elif _is_conc(out) or isinstance(out, _Rng):
        static, detail = False, \
            "apply(x, identity) is independent of x (cannot equal x for all x)"
    else:
        static = None
        detail = "symbolic evaluation too coarse"
    if static is None:
        diags.append(Diagnostic(
            "A001", "info", "apply",
            "identity-fixpoint decided by sampling probe (symbolic "
            "evaluation of the apply at the identity was inconclusive)", ""))
        return PropertyFact(probe_val, "probed", detail)
    if static != probe_val:
        diags.append(Diagnostic(
            "A002", "warning", "apply",
            f"identity-fixpoint: probe says {probe_val}, static evaluation "
            f"says {static} — conservative verdict "
            f"{static and probe_val} is used", ""))
        return PropertyFact(static and probe_val, "static",
                            detail + " (probe disagreed)")
    return PropertyFact(static, "static", detail)


def _decide_absorbing(program, gather_jaxpr, diags) -> PropertyFact:
    probe_val = gather_absorbs_identity(program.gather, program.reduce,
                                        program.value_dtype)
    if gather_jaxpr is None:
        diags.append(Diagnostic(
            "A001", "info", "gather",
            "identity-absorption decided by sampling probe only (gather is "
            "opaque to jaxpr tracing)", ""))
        return PropertyFact(probe_val, "probed", "gather jaxpr opaque")
    ident = _identity_scalar(program)
    state = _EvalState()
    v = np.full((_N,), ident, ident.dtype)
    w = _Rng(-_FIN, _FIN)
    d = _Rng(1.0, float(2**31 - 1))
    out = _eval_closed(gather_jaxpr, [v, w, d], state)
    iscalar = float(ident) if np.issubdtype(ident.dtype, np.floating) \
        else int(ident)
    if _is_conc(out):
        static = bool(out.shape == (_N,) and np.array_equal(
            out, np.full((_N,), ident, out.dtype), equal_nan=True))
        detail = ("gather(identity, w, d) folds to the identity" if static
                  else "gather(identity, w, d) folds to a non-identity value")
    elif isinstance(out, _Rng):
        if out.lo == out.hi == iscalar:
            static, detail = True, "gather(identity) is pinned to the identity"
        elif iscalar < out.lo or iscalar > out.hi:
            static, detail = False, \
                "gather(identity) is bounded away from the identity"
        else:
            static, detail = None, "interval bounds straddle the identity"
    else:
        static, detail = None, "symbolic evaluation too coarse"
    if static is None:
        diags.append(Diagnostic(
            "A001", "info", "gather",
            "identity-absorption decided by sampling probe (symbolic "
            "evaluation of the gather at the identity was inconclusive)", ""))
        return PropertyFact(probe_val, "probed", detail)
    if static != probe_val:
        diags.append(Diagnostic(
            "A002", "warning", "gather",
            f"identity-absorption: probe says {probe_val}, static "
            f"evaluation says {static} — conservative verdict "
            f"{static and probe_val} is used", ""))
        return PropertyFact(static and probe_val, "static",
                            detail + " (probe disagreed)")
    return PropertyFact(static, "static", detail)


def _apply_is_clamp(apply_jaxpr, reduce: str) -> bool:
    """Structurally: is the apply exactly ``min/max(old, reduced)``?"""
    if apply_jaxpr is None or reduce not in ("min", "max"):
        return False
    jaxpr = apply_jaxpr.jaxpr
    live, _ = _live_eqns(jaxpr)
    if len(live) != 1 or len(jaxpr.outvars) != 1:
        return False
    eqn = live[0]
    if eqn.primitive.name != reduce:
        return False
    ops = set()
    for v in eqn.invars:
        if isinstance(v, Literal):
            return False
        ops.add(v)
    return ops == set(jaxpr.invars) and eqn.outvars[0] is jaxpr.outvars[0]


def _decide_monotone(program, gather_jaxpr, apply_jaxpr) -> PropertyFact:
    if program.reduce not in ("min", "max"):
        return PropertyFact(
            False, "static",
            f"reduce '{program.reduce}' has no monotone-convergence "
            "argument (only min/max clamp the value lattice)")
    if not _apply_is_clamp(apply_jaxpr, program.reduce):
        prov = "static" if apply_jaxpr is not None else "declined"
        return PropertyFact(False, prov,
                            "apply is not the reduce's clamp "
                            f"({program.reduce}(old, reduced))")
    if gather_jaxpr is None:
        return PropertyFact(False, "declined",
                            "gather untraceable; no offset-sign evidence")
    state = _EvalState()
    v = _Aff(0, 0.0, 0.0)
    w = _Rng(0.0, _FIN)                  # nonneg weights (validate_graph's
    d = _Rng(1.0, float(2**31 - 1))      # per-reduce weight-domain check)
    out = _eval_closed(gather_jaxpr, [v, w, d], state)
    if isinstance(out, _Aff) and out.var == 0:
        ok = out.lo >= 0 if program.reduce == "min" else out.hi <= 0
        side = ">=" if program.reduce == "min" else "<="
        return PropertyFact(
            ok, "static",
            f"clamp apply; gather offset in [{out.lo:g}, {out.hi:g}] "
            f"({'is' if ok else 'is not'} {side} 0 as '{program.reduce}' "
            "needs)")
    return PropertyFact(False, "static",
                        "gather message is not source-value plus a "
                        "sign-bounded offset")


def _overflow_diags(program, gather_jaxpr, diags) -> None:
    """A003: does ``gather(init)`` wrap the integer value dtype?"""
    dtype = np.dtype(jnp.dtype(program.value_dtype))
    if not np.issubdtype(dtype, np.integer) or dtype.itemsize >= 8:
        return
    init = program.init_value
    if isinstance(init, str) or callable(init) \
            or not (np.isscalar(init) or np.ndim(init) == 0):
        return
    info = np.iinfo(dtype)
    try:
        i = int(init)
    except Exception:
        return
    if i < info.min or i > info.max:
        diags.append(Diagnostic(
            "A003", "error", "init",
            f"init value {i} does not fit the value dtype {dtype} "
            f"(range [{info.min}, {info.max}]) — it wraps before the "
            "first superstep",
            f"use an init below {info.max} or widen value_dtype"))
        return
    if gather_jaxpr is None:
        return
    state = _EvalState()
    v = np.full((_N,), i, dtype)
    w = _Rng(0.0, 8.0)                   # nominal weight magnitudes (the
    d = _Rng(1.0, float(2**31 - 1))      # probes' uniform(1, 8) batch)
    _eval_closed(gather_jaxpr, [v, w, d], state)
    if state.wrapped:
        diags.append(Diagnostic(
            "A003", "error", "gather",
            f"gather evaluated at the init value {i} overflows {dtype} — "
            "messages from unvisited sources silently wrap at runtime",
            f"lower the init (e.g. bfs_program's default 2**30) or widen "
            "value_dtype"))


def _lattice_diags(program, facts: dict, diags) -> None:
    """A004 (absorbing init) and A007 (no termination evidence)."""
    init = program.init_value
    ident = _identity_scalar(program)
    init_is_ident = False
    if not isinstance(init, str) and not callable(init) \
            and (np.isscalar(init) or np.ndim(init) == 0):
        try:
            init_is_ident = bool(np.asarray(init, ident.dtype) == ident)
        except Exception:
            init_is_ident = False
    if init_is_ident and facts["identity_absorbing"].value \
            and facts["identity_fixpoint"].value:
        diags.append(Diagnostic(
            "A004", "warning", "init",
            f"init value equals the '{program.reduce}' reduce identity "
            f"({ident}) everywhere, the gather absorbs it and the apply "
            "fixes it: no superstep changes any vertex until a source is "
            "seeded",
            "seed at least one vertex away from the identity (the "
            "algorithm layer's root injection) before running"))
    if program.frontier == "changed" and program.max_iters is None \
            and not facts["monotone"].value:
        diags.append(Diagnostic(
            "A007", "info", "program",
            "frontier='changed' with no max_iters and no "
            "monotone-convergence evidence — only the superstep budget "
            "bounds the run",
            "set max_iters, or use a min/max clamp apply the analyzer "
            "can prove monotone"))


# ---------------------------------------------------------------------------
# analyze_program (cached)
# ---------------------------------------------------------------------------

_ANALYSIS_CACHE: OrderedDict = OrderedDict()
_ANALYSIS_CACHE_MAX = 64


def analysis_cache_clear() -> None:
    """Drop all memoized :class:`ProgramAnalysis` results (tests)."""
    _ANALYSIS_CACHE.clear()


def analyze_program(program: VertexProgram) -> ProgramAnalysis:
    """Analyze a vertex program's gather/apply statically (cached).

    Traces both callables to jaxprs on abstract ``(8,)`` avals and
    decides every property the pass pipeline needs (see
    :class:`ProgramAnalysis`); opaque callables fall back to the legacy
    sampling probes with ``provenance='probed'`` and an ``A001``
    diagnostic.  Results are memoized per program object — the DSL's
    template factories are themselves memoized, so the natural
    ``translate(dsl.bfs_program(), ...)`` repeat pattern hits this cache
    and repeat translations pay zero analysis cost.
    """
    try:
        hash(program)                    # unhashable programs skip the cache
        key = program
    except TypeError:
        key = None
    if key is not None and key in _ANALYSIS_CACHE:
        _ANALYSIS_CACHE.move_to_end(key)
        return _ANALYSIS_CACHE[key]

    dtype = program.value_dtype
    gather_jaxpr = _trace(program.gather, *_gather_avals(dtype))
    apply_jaxpr = _trace(program.apply, *_apply_avals(dtype))
    diags: list = []

    facts = {
        "gather_module": _decide_module(program, gather_jaxpr, diags),
        "weight_use": _decide_weight_use(program, gather_jaxpr),
        "elementwise": _decide_elementwise(program, apply_jaxpr, diags),
        "identity_fixpoint": _decide_fixpoint(program, apply_jaxpr, diags),
        "identity_absorbing": _decide_absorbing(program, gather_jaxpr, diags),
    }
    facts["monotone"] = _decide_monotone(program, gather_jaxpr, apply_jaxpr)
    _overflow_diags(program, gather_jaxpr, diags)
    _lattice_diags(program, facts, diags)

    result = ProgramAnalysis(diagnostics=tuple(diags), **facts)
    if key is not None:
        _ANALYSIS_CACHE[key] = result
        while len(_ANALYSIS_CACHE) > _ANALYSIS_CACHE_MAX:
            _ANALYSIS_CACHE.popitem(last=False)
    return result


# ---------------------------------------------------------------------------
# IR verifier (structural invariants between passes)
# ---------------------------------------------------------------------------

# Canonical superstep order: each op class's rank; ranks must be
# non-decreasing along ``ir.ops``.  Fused classes share the rank of the
# stage they replace.
_OP_RANK = {
    "GatherOp": 0,
    "FusedGatherReduceOp": 1,
    "FusedSuperstepOp": 1,
    "ReduceOp": 2,
    "PushScatterOp": 3,
    "ExchangeOp": 4,
    "ApplyOp": 5,
    "FrontierUpdateOp": 6,
}

_EXCHANGE_COLLECTIVES = {"add": "psum", "min": "pmin", "max": "pmax"}
_BACKENDS = ("dense_pallas", "dense_xla", "sparse_xla")


def _v(code, op, message, suggestion=""):
    return Diagnostic(code, "error", op, message, suggestion)


def verify_ir(ir, ctx=None) -> list:
    """Check :class:`~repro.core.ir.SuperstepIR` structural invariants.

    Returns a list of error-severity ``V*`` :class:`Diagnostic`\\ s —
    empty for a well-formed IR.  ``PassPipeline.run(..., verify=True)``
    calls this between every pass pair and raises
    :class:`~repro.errors.IRVerificationError` on the first non-empty
    result, naming the offending pass boundary.  ``ctx`` (a
    ``PassContext``) enables the plan-agreement checks (V007).
    """
    from .ir import (ApplyOp, ExchangeOp, FrontierUpdateOp,
                     FusedGatherReduceOp, FusedSuperstepOp, GatherOp,
                     PushScatterOp, ReduceOp)

    program = ir.program
    out = []
    counts: dict = {}
    for op in ir.ops:
        counts[type(op).__name__] = counts.get(type(op).__name__, 0) + 1

    # V001 — op multiplicity
    for name, n in counts.items():
        if n > 1:
            out.append(_v("V001", name, f"{n} {name} ops in one superstep "
                          "(each stage appears at most once)"))
    gather_planes = sum(counts.get(n, 0) for n in
                        ("GatherOp", "FusedGatherReduceOp",
                         "FusedSuperstepOp"))
    if gather_planes != 1:
        out.append(_v("V001", "Gather",
                      f"{gather_planes} gather-plane ops (exactly one of "
                      "Gather/FusedGatherReduce/FusedSuperstep required)"))
    fused_any = counts.get("FusedGatherReduceOp", 0) \
        + counts.get("FusedSuperstepOp", 0)
    if bool(counts.get("ReduceOp", 0)) == bool(fused_any):
        out.append(_v("V001", "Reduce",
                      "ReduceOp must be present exactly when no fused "
                      "gather+reduce op is"))
    step = ir.find(FusedSuperstepOp)
    for name in ("ApplyOp", "FrontierUpdateOp"):
        if bool(counts.get(name, 0)) == (step is not None):
            out.append(_v("V001", name,
                          f"{name} must be present exactly when the "
                          "superstep is not fused into one stage"))

    # V002 — op ordering
    ranks = [_OP_RANK.get(type(op).__name__) for op in ir.ops]
    if None in ranks:
        bad = type(ir.ops[ranks.index(None)]).__name__
        out.append(_v("V002", bad, f"unknown op class {bad} in the IR"))
    elif any(a > b for a, b in zip(ranks, ranks[1:])):
        out.append(_v("V002", "ops",
                      "ops out of canonical superstep order "
                      "(gather → reduce → push → exchange → apply → "
                      "frontier): "
                      + " -> ".join(type(o).__name__ for o in ir.ops)))

    # gather/reduce views (direct or through fused wrappers)
    gop = ir.find(GatherOp)
    fgr = ir.find(FusedGatherReduceOp)
    if step is not None:
        fgr = step.fused
    if gop is None and fgr is not None:
        gop = fgr.gather
    rop = ir.find(ReduceOp)
    if rop is None and fgr is not None:
        rop = fgr.reduce

    # V003 — reduce consistency
    if rop is not None:
        if rop.op != program.reduce:
            out.append(_v("V003", "Reduce",
                          f"reduce op {rop.op!r} disagrees with the "
                          f"program's {program.reduce!r}"))
        if rop.identity is not None:
            want = reduce_identity(rop.op, ir.value_dtype)
            ok = jnp.dtype(jnp.asarray(rop.identity).dtype) == \
                jnp.dtype(ir.value_dtype) and bool(
                    np.array_equal(np.asarray(rop.identity),
                                   np.asarray(want), equal_nan=True))
            if not ok:
                out.append(_v(
                    "V003", "Reduce",
                    f"folded identity {rop.identity!r} is not "
                    f"reduce_identity({rop.op!r}, {jnp.dtype(ir.value_dtype)})"))

    # V004 — gather module annotation
    if gop is not None and gop.module is not None \
            and gop.module not in GATHER_OPS:
        out.append(_v("V004", "Gather",
                      f"annotated module {gop.module!r} names no menu "
                      f"module (menu: {', '.join(GATHER_OPS)})"))

    # V005 — direction legality preconditions
    push = ir.find(PushScatterOp)
    direction = gop.direction if gop is not None else "pull"
    if fgr is not None:
        direction = fgr.direction
    if direction == "both" or push is not None:
        reasons = []
        if program.reduce not in _COMMUTATIVE_REDUCES:
            reasons.append(f"reduce {program.reduce!r} is not commutative")
        if program.reduce == "add" and \
                jnp.issubdtype(ir.value_dtype, jnp.floating):
            reasons.append("float add is order-sensitive")
        if not program.mask_inactive:
            reasons.append("mask_inactive=False")
        if program.frontier != "changed":
            reasons.append(f"frontier={program.frontier!r}")
        if reasons:
            out.append(_v("V005", "PushScatter" if push else "Gather",
                          "push direction bound without its preconditions: "
                          + "; ".join(reasons)))

    # V006 — backend/kernel agreement
    if ir.backend is not None:
        if ir.backend not in _BACKENDS:
            out.append(_v("V006", "backend",
                          f"unknown backend {ir.backend!r}"))
        if fgr is not None:
            want = "edge_block" if ir.backend.startswith("dense") \
                else "segment_scan"
            if fgr.kernel != want:
                out.append(_v("V006", "FusedGatherReduce",
                              f"kernel {fgr.kernel!r} disagrees with "
                              f"backend {ir.backend!r} (expected {want!r})"))
        if push is not None and push.layout == "fwd_ell" \
                and not ir.backend.startswith("dense"):
            out.append(_v("V006", "PushScatter",
                          "fwd_ell push layout needs a dense backend "
                          f"(backend is {ir.backend!r})"))
    elif fgr is not None:
        out.append(_v("V006", "FusedGatherReduce",
                      "gather+reduce fused before a backend was resolved"))

    # V007 — exchange-plane consistency
    xop = ir.find(ExchangeOp)
    if xop is not None:
        if xop.reduce != program.reduce:
            out.append(_v("V007", "Exchange",
                          f"exchange reduce {xop.reduce!r} disagrees with "
                          f"the program's {program.reduce!r}"))
        if xop.collective is not None:
            want = _EXCHANGE_COLLECTIVES.get(xop.reduce)
            if xop.collective != want:
                out.append(_v("V007", "Exchange",
                              f"collective {xop.collective!r} is not the "
                              f"reduce-matched {want!r}"))
            if xop.pes is None or xop.pes <= 1:
                out.append(_v("V007", "Exchange",
                              "resolved collective with pes <= 1 (a "
                              "single-PE exchange should be elided)"))
            if ctx is not None and xop.pes is not None \
                    and xop.pes != ctx.plan.pes:
                out.append(_v("V007", "Exchange",
                              f"exchange pes={xop.pes} disagrees with the "
                              f"schedule plan's pes={ctx.plan.pes}"))

    # V008 — frontier consistency
    fop = ir.find(FrontierUpdateOp)
    if fop is None and step is not None:
        fop = step.frontier
    if fop is not None:
        if fop.mode != program.frontier:
            out.append(_v("V008", "FrontierUpdate",
                          f"frontier mode {fop.mode!r} disagrees with the "
                          f"program's {program.frontier!r}"))
        if fop.dead and fop.mode != "all":
            out.append(_v("V008", "FrontierUpdate",
                          "frontier marked dead but mode is "
                          f"{fop.mode!r} (only 'all' frontiers are dead)"))

    # V009 — fused-superstep binding preconditions
    if step is not None:
        if step.pull_sweep == "bitmap":
            reasons = []
            if not program.mask_inactive:
                reasons.append("mask_inactive=False")
            if program.frontier != "changed":
                reasons.append(f"frontier={program.frontier!r}")
            if not (ir.backend or "").startswith("dense"):
                reasons.append(f"backend {ir.backend!r} is not dense")
            if reasons:
                out.append(_v("V009", "FusedSuperstep",
                              "bitmap pull sweep bound without its "
                              "preconditions: " + "; ".join(reasons)))
        if step.touched_free and program.frontier != "changed":
            out.append(_v("V009", "FusedSuperstep",
                          "touched-mask elision bound with "
                          f"frontier={program.frontier!r} (needs "
                          "'changed')"))
    return out
