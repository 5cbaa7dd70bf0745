"""Semantic analysis of user ``reduce`` functions via jaxpr inspection.

This is the JAX-native analogue of MR4J's Java-agent bytecode analysis
(paper §3.1.1/§3.2): where MR4J parses reduce-method bytecode into a program
dependency graph and copies adjusted bytecodes into generated
``initialize``/``combine``/``finalize`` methods, we trace the user's reduce
function to a jaxpr and slice it into

    ``premap`` (elementwise, per emitted value — map-side)
  ∘ ``monoid reduction frontier`` (reduce_sum/max/min/prod/and/or, or a
    lax.scan fold, or the paper's two idioms: first-element and size-only)
  ∘ ``finalize`` (arbitrary post-processing of the reduced scalars).

The contract (identical to the paper's):  ``reduce(key, values, count)`` where
``values`` has shape ``[L, *value_shape]``, entries ``values[count:]`` are the
app-declared pad value, and the reduction must be insensitive to the order of
values (MapReduce semantics).  The analyzer never *executes* user code with
real data; it works on abstract values, like the paper's class-load-time
transformation.

Key invariant used throughout: a var is *tainted* iff its value varies with
the position along the values axis.  Untainted vars that carry the L axis are
only accepted when produced by a broadcast INTO axis 0 (uniform along L), so
dropping the axis is always sound for the streaming rewrite.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend import core as jax_core

from repro.core import combiner as C

# ---------------------------------------------------------------------------
# Primitive tables
# ---------------------------------------------------------------------------

#: value-axis reduction primitive -> monoid (the frontier the paper's
#: optimizer maps onto its ``combine`` method).
REDUCE_MONOIDS = {
    "reduce_sum": C.ADD,
    "reduce_prod": C.MUL,
    "reduce_max": C.MAX,
    "reduce_min": C.MIN,
    "reduce_and": C.AND,
    "reduce_or": C.OR,
}

#: elementwise primitives allowed in the premap slice (position-preserving
#: along the values axis).  Mirrors the paper's "adjusted bytecodes" that are
#: copied verbatim into the generated combine method.
ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow",
    "max", "min", "exp", "exp2", "log", "log1p", "expm1",
    "tanh", "sin", "cos", "tan", "asin", "acos", "atan", "atan2",
    "sinh", "cosh", "logistic", "sqrt", "rsqrt", "cbrt",
    "neg", "abs", "sign", "floor", "ceil", "round", "is_finite",
    "not", "and", "or", "xor",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "eq", "ne", "lt", "gt", "le", "ge", "select_n",
    "convert_element_type", "erf", "erfc", "erf_inv", "clamp",
    "nextafter", "copy", "reduce_precision", "stop_gradient", "square",
}

#: call-like primitives we transparently recurse into (inline).
CALL_PRIMS = {"jit", "pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
              "custom_jvp_call_jaxpr", "remat", "checkpoint"}


class ExtractionFailure(Exception):
    """Raised when the reduce fn cannot be sliced into a combiner triple."""


def _sub_jaxpr(eqn):
    p = eqn.params
    for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if k in p:
            return p[k]
    raise ExtractionFailure(f"call primitive {eqn.primitive.name} without jaxpr")


def _is_lit(v) -> bool:
    return hasattr(v, "val")


# ---------------------------------------------------------------------------
# Inlining: flatten call-like eqns so the analysis sees one flat jaxpr.
# ---------------------------------------------------------------------------


def _inline(jaxpr, consts):
    """Flatten (jaxpr, consts) -> (eqns, const_env, invars, outvars).

    A called jaxpr's equations get fresh output vars at each call site:
    JAX caches the jaxpr of a jitted helper (``jnp.where`` is one), so two
    calls of it would otherwise share, and overwrite, the same vars."""
    const_env: dict[Any, Any] = {}
    flat_eqns: list = []

    def go(jx, jconsts, sub: dict, inner: bool):
        for cv, cval in zip(jx.constvars, jconsts):
            const_env[cv] = cval

        def resolve(v):
            return v if _is_lit(v) else sub.get(v, v)

        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in CALL_PRIMS:
                cj = _sub_jaxpr(eqn)
                inner_jx, inner_consts = cj.jaxpr, cj.consts
                inner_sub: dict = {}
                args = [resolve(v) for v in eqn.invars]
                n = min(len(inner_jx.invars), len(args))
                for iv, av in zip(inner_jx.invars[:n], args[:n]):
                    inner_sub[iv] = av
                go(inner_jx, inner_consts, inner_sub, True)
                for ov, inner_ov in zip(eqn.outvars, inner_jx.outvars):
                    sub[ov] = (inner_ov if _is_lit(inner_ov)
                               else inner_sub.get(inner_ov, inner_ov))
            else:
                outvars = eqn.outvars
                if inner:
                    outvars = [jax_core.Var(v.aval) if type(v) is jax_core.Var
                               else v for v in outvars]  # not a DropVar
                    sub.update(zip(eqn.outvars, outvars))
                flat_eqns.append(eqn.replace(
                    invars=[resolve(v) for v in eqn.invars],
                    outvars=outvars))

    top_sub: dict = {}
    go(jaxpr, consts, top_sub, False)
    outvars = [v if _is_lit(v) else top_sub.get(v, v) for v in jaxpr.outvars]
    return flat_eqns, const_env, list(jaxpr.invars), outvars


# ---------------------------------------------------------------------------
# Frontier description
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Frontier:
    kind: str  # "monoid" | "first" | "scan"
    eqn: Any
    monoid: C.Monoid | None = None
    #: for monoid frontiers: reduction axes other than the L axis (already
    #: shifted by -1 into dropped-value coordinates); applied in the premap.
    extra_axes: tuple[int, ...] = ()


@dataclasses.dataclass
class Analysis:
    """Everything the optimizer needs to synthesize a CombinerSpec."""

    eqns: list
    const_env: dict
    invars: list  # [key, values, count]
    outvars: list
    tainted: set
    frontiers: list
    premap_ids: set  # id(eqn) of tainted pre-frontier eqns (in eqns order)
    producer: dict  # var -> eqn
    value_aval: jax.ShapeDtypeStruct
    max_len: int

    @property
    def premap_eqns(self):
        return [e for e in self.eqns if id(e) in self.premap_ids]


# ---------------------------------------------------------------------------
# The analyzer
# ---------------------------------------------------------------------------


def analyze(
    reduce_fn: Callable,
    key_aval: Any,
    value_aval: jax.ShapeDtypeStruct,
    *,
    max_len: int = 8,
) -> Analysis:
    """Trace + slice ``reduce_fn(key, values, count)``.

    Raises :class:`ExtractionFailure` if the function is not expressible as
    premap ∘ frontier ∘ finalize under the rules in the module docstring.
    """
    values_aval = jax.ShapeDtypeStruct((max_len,) + tuple(value_aval.shape),
                                       value_aval.dtype)
    count_aval = jax.ShapeDtypeStruct((), jnp.int32)
    closed = jax.make_jaxpr(reduce_fn)(key_aval, values_aval, count_aval)
    eqns, const_env, invars, outvars = _inline(closed.jaxpr, closed.consts)
    if len(invars) != 3:
        raise ExtractionFailure("reduce must take exactly (key, values, count)")
    key_var, values_var, count_var = invars

    tainted: set = {values_var}
    count_tainted: set = {count_var}
    key_tainted: set = {key_var}
    frontiers: list[Frontier] = []
    premap_ids: set = set()
    producer: dict = {}
    L = max_len

    def any_in(vars_, s):
        return any((not _is_lit(v)) and v in s for v in vars_)

    def check_uniform_operands(eqn):
        """Untainted operands of a premap eqn must be safe to L-drop."""
        for v in eqn.invars:
            if _is_lit(v) or v in tainted:
                continue
            shape = tuple(v.aval.shape)
            if not shape or shape[0] != L:
                continue  # no L axis: scalar/trailing-broadcast, safe as-is
            prod = producer.get(v)
            ok = (
                prod is not None
                and prod.primitive.name == "broadcast_in_dim"
                and 0 not in tuple(prod.params["broadcast_dimensions"])
            )
            if not ok:
                raise ExtractionFailure(
                    f"{eqn.primitive.name}: untainted operand carries the "
                    "values axis but is not a uniform broadcast (possible "
                    "position-dependent input, e.g. iota)")

    for eqn in eqns:
        for ov in eqn.outvars:
            producer[ov] = eqn
        name = eqn.primitive.name
        in_tainted = any_in(eqn.invars, tainted)
        if not in_tainted:
            if any_in(eqn.invars, count_tainted):
                count_tainted.update(eqn.outvars)
            if any_in(eqn.invars, key_tainted):
                key_tainted.update(eqn.outvars)
            continue

        # ----- tainted eqn: must be premap-elementwise or a frontier -----
        if any_in(eqn.invars, count_tainted):
            raise ExtractionFailure(
                f"{name}: count flows into the per-value (map-side) slice; "
                "a streaming combine cannot know the final count")
        if any_in(eqn.invars, key_tainted):
            raise ExtractionFailure(
                f"{name}: key flows into the per-value slice (keyed premap "
                "unsupported)")

        def accept_premap():
            check_uniform_operands(eqn)
            premap_ids.add(id(eqn))
            tainted.update(eqn.outvars)

        if name in REDUCE_MONOIDS:
            axes = tuple(eqn.params["axes"])
            (operand,) = eqn.invars
            if operand.aval.shape[:1] != (L,):
                raise ExtractionFailure(f"{name}: operand lost the values axis")
            if 0 in axes:
                extra = tuple(a - 1 for a in axes if a != 0)
                frontiers.append(Frontier("monoid", eqn,
                                          monoid=REDUCE_MONOIDS[name],
                                          extra_axes=extra))
                continue  # frontier output is clean
            accept_premap()  # positionwise reduction over value dims
            continue

        if name == "slice":
            starts = tuple(eqn.params["start_indices"])
            limits = tuple(eqn.params["limit_indices"])
            strides = eqn.params.get("strides")
            op = eqn.invars[0]
            stride_ok = strides is None or all(s == 1 for s in strides)
            if (op.aval.shape[:1] == (L,) and starts[0] == 0 and limits[0] == L
                    and stride_ok):
                accept_premap()  # trailing-dim slice, e.g. values[:, 0:1]
                continue
            first_elem = (
                op.aval.shape[:1] == (L,) and starts[0] == 0 and limits[0] == 1
                and starts[1:] == (0,) * (len(starts) - 1)
                and limits[1:] == tuple(op.aval.shape[1:]) and stride_ok
            )
            if first_elem:
                frontiers.append(Frontier("first", eqn))  # paper idiom 1
                continue
            raise ExtractionFailure("slice of values other than values[0] / "
                                    "full-axis trailing slices")

        if name == "squeeze":
            dims = tuple(eqn.params["dimensions"])
            if 0 not in dims:
                accept_premap()
                continue
            raise ExtractionFailure("squeeze removes the values axis")

        if name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            if any_in(eqn.invars[:nc + nk], tainted):
                raise ExtractionFailure("values flow into scan consts/init")
            frontiers.append(Frontier("scan", eqn))
            continue

        if name in ELEMENTWISE:
            for v in eqn.invars:
                if not _is_lit(v) and v in tainted and v.aval.shape[:1] != (L,):
                    raise ExtractionFailure(f"{name}: tainted operand lost L axis")
            accept_premap()
            continue

        if name == "broadcast_in_dim":
            bd = tuple(eqn.params["broadcast_dimensions"])
            shape = tuple(eqn.params["shape"])
            if bd[:1] == (0,) and shape[:1] == (L,):
                accept_premap()
                continue
            raise ExtractionFailure("broadcast moves/duplicates the L axis")

        if name == "transpose":
            if tuple(eqn.params["permutation"])[:1] == (0,):
                accept_premap()
                continue
            raise ExtractionFailure("transpose moves the L axis")

        if name == "reshape":
            if (tuple(eqn.params["new_sizes"])[:1] == (L,)
                    and eqn.params.get("dimensions") is None):
                accept_premap()
                continue
            raise ExtractionFailure("reshape folds the L axis")

        raise ExtractionFailure(f"primitive {name} not allowed on values")

    if any_in(outvars, tainted):
        raise ExtractionFailure("raw values escape to the reducer output")
    if sum(1 for f in frontiers if f.kind == "scan") > 1:
        raise ExtractionFailure("multiple scan folds unsupported")
    if any(f.kind == "scan" for f in frontiers) and len(frontiers) != 1:
        raise ExtractionFailure("scan fold mixed with other frontiers")

    # scan ys outputs must be dead (streaming combine has no per-step output)
    for f in frontiers:
        if f.kind != "scan":
            continue
        e = f.eqn
        nk = e.params["num_carry"]
        ys = set(e.outvars[nk:])
        if ys:
            used = set()
            for other in eqns:
                if other is e:
                    continue
                used.update(v for v in other.invars if not _is_lit(v))
            used.update(v for v in outvars if not _is_lit(v))
            if ys & used:
                raise ExtractionFailure("scan per-step outputs (ys) are used")

    return Analysis(
        eqns=eqns, const_env=const_env, invars=invars, outvars=outvars,
        tainted=tainted, frontiers=frontiers, premap_ids=premap_ids,
        producer=producer, value_aval=value_aval, max_len=L,
    )


# ---------------------------------------------------------------------------
# Surgical evaluators — the generated method bodies (paper Fig 4).
# ---------------------------------------------------------------------------


def _bind_dropped(eqn, args):
    """Evaluate a premap eqn with the L axis dropped from its operands."""
    name = eqn.primitive.name
    params = dict(eqn.params)
    if name == "broadcast_in_dim":
        params["shape"] = tuple(eqn.params["shape"])[1:]
        params["broadcast_dimensions"] = tuple(
            d - 1 for d in eqn.params["broadcast_dimensions"][1:])
    elif name == "transpose":
        params["permutation"] = tuple(
            p - 1 for p in eqn.params["permutation"][1:])
    elif name == "reshape":
        params["new_sizes"] = tuple(eqn.params["new_sizes"])[1:]
    elif name == "slice":
        params["start_indices"] = tuple(eqn.params["start_indices"])[1:]
        params["limit_indices"] = tuple(eqn.params["limit_indices"])[1:]
        if eqn.params.get("strides") is not None:
            params["strides"] = tuple(eqn.params["strides"])[1:]
    elif name == "squeeze":
        params["dimensions"] = tuple(
            d - 1 for d in eqn.params["dimensions"])
    elif name in REDUCE_MONOIDS:  # positionwise reduction over value dims
        params["axes"] = tuple(a - 1 for a in eqn.params["axes"])
    out = eqn.primitive.bind(*args, **params)
    return out if eqn.primitive.multiple_results else [out]


def frontier_channels(an: Analysis) -> list[tuple[Frontier, Any]]:
    """(frontier, input var) per premap channel; scan xs expand to several."""
    chans = []
    for f in an.frontiers:
        if f.kind == "scan":
            e = f.eqn
            nc, nk = e.params["num_consts"], e.params["num_carry"]
            for v in e.invars[nc + nk:]:
                chans.append((f, v))
        else:
            chans.append((f, f.eqn.invars[0]))
    return chans


def build_premap(an: Analysis) -> Callable:
    """premap(v) -> tuple of frontier input channels (dropped-L shapes).

    This is the map-side slice MR4J copies into ``combine`` before the fold.
    """
    chans = frontier_channels(an)
    values_var = an.invars[1]
    const_env = an.const_env
    premap_ids = an.premap_ids
    tainted = an.tainted
    producer = an.producer
    L = an.max_len

    def premap(v):
        env: dict = {values_var: v}

        def read(x):
            if _is_lit(x):
                return x.val
            if x in env:
                return env[x]
            if x in const_env:
                val = const_env[x]
                if jnp.ndim(val) and jnp.shape(val)[0] == L:
                    raise ExtractionFailure(
                        "captured [L]-shaped constant in premap")
                return val
            # untainted intermediate: evaluate its (constant) producer chain
            prod = producer.get(x)
            if prod is None:
                raise ExtractionFailure(f"premap: unbound var {x}")
            args = [read(a) for a in prod.invars]
            if (prod.primitive.name == "broadcast_in_dim"
                    and tuple(prod.params["shape"])[:1] == (L,)
                    and 0 not in tuple(prod.params["broadcast_dimensions"])):
                # uniform broadcast into the L axis: drop it
                params = dict(prod.params)
                params["shape"] = tuple(prod.params["shape"])[1:]
                params["broadcast_dimensions"] = tuple(
                    d - 1 for d in prod.params["broadcast_dimensions"])
                outs = [prod.primitive.bind(*args, **params)]
            else:
                o = prod.primitive.bind(*args, **prod.params)
                outs = o if prod.primitive.multiple_results else [o]
            for ov, oval in zip(prod.outvars, outs):
                env[ov] = oval
            return env[x]

        for eqn in an.eqns:
            if id(eqn) not in premap_ids:
                continue
            args = [read(x) for x in eqn.invars]
            outs = _bind_dropped(eqn, args)
            for ov, o in zip(eqn.outvars, outs):
                env[ov] = o

        out = []
        for f, iv in chans:
            x = read(iv)
            if f.kind == "monoid" and f.extra_axes:
                x = lax.reduce(x, np.asarray(f.monoid.identity(x.dtype)),
                               f.monoid.op, f.extra_axes)
            out.append(x)
        return tuple(out)

    return premap


def build_finalize(an: Analysis, holder_slots: Sequence[Sequence[Any]]) -> Callable:
    """finalize(key, holders, count) -> reducer output.

    ``holder_slots[i]`` lists the frontier-i outvars to substitute with the
    corresponding holder leaves (monoid/first: 1 var; scan: num_carry vars).
    Demand-driven: eqns feeding only the premap slice are skipped.
    """
    key_var, values_var, count_var = an.invars
    const_env = an.const_env
    frontier_eqn_ids = {id(f.eqn) for f in an.frontiers}
    premap_ids = an.premap_ids

    def finalize(key, holders, count):
        env: dict = {key_var: key, count_var: count}
        env.update(const_env)
        for slots, leaves in zip(holder_slots, holders):
            hl = list(leaves) if isinstance(leaves, (list, tuple)) else [leaves]
            for var, leaf in zip(slots, hl):
                # re-add dims the trace expects (first idiom keeps [1, ...]);
                # different-SIZED leaves pass through unchanged — elementwise
                # finalizes are shape-polymorphic (used by grad accumulation
                # to apply a spec derived on a small proxy aval).
                want = tuple(var.aval.shape)
                have = tuple(jnp.shape(leaf))
                if want != have and int(np.prod(want)) == int(np.prod(have)):
                    leaf = jnp.reshape(leaf, want)
                env[var] = leaf

        def read(x):
            if _is_lit(x):
                return x.val
            return env[x]

        for eqn in an.eqns:
            if id(eqn) in frontier_eqn_ids or id(eqn) in premap_ids:
                continue
            try:
                args = [read(x) for x in eqn.invars]
            except KeyError:
                continue  # feeds only the premap slice
            out = eqn.primitive.bind(*args, **eqn.params)
            outs = out if eqn.primitive.multiple_results else [out]
            for ov, o in zip(eqn.outvars, outs):
                env[ov] = o

        res = [read(v) for v in an.outvars]
        return res[0] if len(res) == 1 else tuple(res)

    return finalize


def eval_const_operands(an: Analysis, vars_: Sequence[Any]) -> list:
    """Evaluate vars that must be constants (scan consts / carry inits)."""
    env: dict = dict(an.const_env)

    def read(x):
        if _is_lit(x):
            return x.val
        if x in env:
            return env[x]
        prod = an.producer.get(x)
        if prod is None or any_tainted(prod):
            raise ExtractionFailure(
                "scan const/init is not a trace-time constant")
        args = [read(a) for a in prod.invars]
        out = prod.primitive.bind(*args, **prod.params)
        outs = out if prod.primitive.multiple_results else [out]
        for ov, o in zip(prod.outvars, outs):
            env[ov] = o
        return env[x]

    def any_tainted(eqn):
        return any((not _is_lit(v)) and v in an.tainted for v in eqn.invars)

    return [read(v) for v in vars_]
