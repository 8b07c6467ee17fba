"""jaxpr -> StitchIR frontend.

``trace_to_graph(fn, *example_args)`` traces a JAX function with abstract
values and translates the closed jaxpr into a :class:`Graph`, so the fusion
planner runs on real model code, not just hand-built graphs (the paper sits
inside XLA and consumes HLO; this is our equivalent entry point).

Coverage: the elementwise / broadcast / reshape / transpose / reduction /
dot_general / gather vocabulary of StitchIR, with ``pjit``/``custom_jvp`` /
``custom_vjp`` calls inlined, plus the scatter family (first-class SCATTER
nodes — the transpose of gather, surfaced by every embedding-table gradient).
Any other primitive becomes an executable CUSTOM node (it partitions fusion —
same role as the paper's opaque ops — but the graph stays runnable
end-to-end because the node carries a closure evaluating the original
primitive); backward passes built by ``jax.value_and_grad`` trace through
the same entry point as forward code.

Shard-local functions (``shard_map`` bodies) that contain collectives —
``psum``/``pmean``/``all_gather`` over a named mesh axis — trace with
``axis_env=[(axis, size), ...]``: the collective becomes an executable
CUSTOM node (a fusion partition, exactly like the paper's opaque ops, which
is also the right cost-model story: a collective is a data-movement barrier
no kernel fusion may cross) whose closure re-binds the primitive, so the
compiled artifact runs *inside* ``shard_map`` where the axis names are live.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend import core as jcore

from .ir import Graph, OpKind, OpNode

__all__ = ["trace_to_graph", "TraceError"]


class TraceError(Exception):
    pass


_EW_PRIMS = {
    "add": "add", "sub": "sub", "mul": "mul", "div": "div",
    "max": "max", "min": "min", "pow": "pow", "neg": "neg",
    "exp": "exp", "log": "log", "log1p": "log1p", "tanh": "tanh",
    "sqrt": "sqrt", "rsqrt": "rsqrt", "abs": "abs", "sign": "sign",
    "erf": "erf", "logistic": "sigmoid", "square": "square",
    "cos": "cos", "sin": "sin",
    "ge": "ge", "gt": "gt", "le": "le", "lt": "lt", "eq": "eq",
    "and": "and", "or": "or", "not": "not", "xor": "xor",
    # backward-only spellings: the grad-accumulation add (symbolic-zero aware)
    # is an ordinary add once both operands are materialized
    "add_any": "add",
}

_REDUCE_PRIMS = {
    "reduce_sum": "sum", "reduce_max": "max",
    "reduce_min": "min", "reduce_prod": "prod",
}

_INLINE_CALLS = {"pjit", "jit", "custom_jvp_call", "custom_vjp_call",
                 "custom_jvp_call_jaxpr", "remat", "checkpoint", "closed_call"}

# Backward-only data movement: the transpose of gather/dynamic-slice is a
# scatter(-add), so every embedding-table gradient surfaces one.  They get a
# first-class SCATTER kind (the planner already treats SCATTER as a fusion
# partition op) but stay executable through the same closure mechanism as
# CUSTOM nodes.
_SCATTER_PRIMS = {"scatter", "scatter-add", "scatter-mul",
                  "scatter-min", "scatter-max"}


def _dtype_str(aval) -> str:
    return str(np.dtype(aval.dtype))


def _stable_params_sig(params: dict) -> str:
    """Deterministic, value-level spelling of a primitive's static params.

    CUSTOM nodes execute through a closure (``eval_fn``), which no hash can
    see; this canonicalization hook preserves the *identity* of the opaque
    op (dimension numbers, window shapes, ...) so graph signatures
    (:mod:`repro.cache.signature`) distinguish configurations while staying
    invariant to node naming and trace order.  Arrays and sub-jaxprs are
    spelled by dtype/rank only — their content is runtime detail.
    """
    def spell(v) -> str:
        if isinstance(v, (bool, int, float, str, type(None))):
            return repr(v)
        if isinstance(v, (tuple, list)):
            return "(" + ",".join(spell(x) for x in v) + ")"
        if isinstance(v, np.ndarray):
            return f"array:{v.dtype}:rank{v.ndim}"
        if isinstance(v, np.dtype) or isinstance(v, type):
            return str(v)
        return type(v).__name__
    return ";".join(f"{k}={spell(params[k])}" for k in sorted(params))


def trace_to_graph(fn: Callable, *example_args, name: str = "traced",
                   axis_env=None) -> tuple[Graph, list[str]]:
    """Returns (graph, input_names) where input_names[i] is the PARAMETER
    node for positional argument i (flattened pytree order).

    ``axis_env``: sequence of ``(axis_name, size)`` pairs making mesh axes
    visible to the trace, for shard-local functions containing collectives
    (see module docstring)."""
    if axis_env:
        closed = jax.make_jaxpr(fn, axis_env=list(axis_env))(*example_args)
    else:
        closed = jax.make_jaxpr(fn)(*example_args)
    g = Graph(name)
    fresh_ctr = [0]

    def fresh(stem: str) -> str:
        fresh_ctr[0] += 1
        return f"{stem}_{fresh_ctr[0]}"

    env: dict = {}

    def read(var) -> str:
        if isinstance(var, jcore.Literal):
            val = np.asarray(var.val)
            nm = fresh("lit")
            g.add(OpNode(nm, OpKind.CONSTANT, tuple(val.shape), str(val.dtype),
                         (), {"value": val}))
            return nm
        return env[var]

    input_names: list[str] = []
    flat_args = jax.tree_util.tree_leaves(example_args)
    for i, v in enumerate(closed.jaxpr.invars):
        nm = f"arg{i}"
        g.add(OpNode(nm, OpKind.PARAMETER, tuple(v.aval.shape), _dtype_str(v.aval)))
        env[v] = nm
        input_names.append(nm)
    for v, val in zip(closed.jaxpr.constvars, closed.consts):
        nm = fresh("const")
        arr = np.asarray(val)
        g.add(OpNode(nm, OpKind.CONSTANT, tuple(arr.shape), str(arr.dtype),
                     (), {"value": arr}))
        env[v] = nm

    def emit_eqn(eqn) -> None:
        prim = eqn.primitive.name
        if prim in _INLINE_CALLS:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is None and prim == "custom_jvp_call":
                sub = eqn.params.get("fun_jaxpr")
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                consts = getattr(sub, "consts", eqn.params.get("consts", ()))
                for cv, cval in zip(inner.constvars, consts):
                    nm = fresh("const")
                    arr = np.asarray(cval)
                    g.add(OpNode(nm, OpKind.CONSTANT, tuple(arr.shape),
                                 str(arr.dtype), (), {"value": arr}))
                    env[cv] = nm
                for iv, outer in zip(inner.invars, eqn.invars):
                    env[iv] = read(outer)
                for ieqn in inner.eqns:
                    emit_eqn(ieqn)
                for ov, iv in zip(eqn.outvars, inner.outvars):
                    env[ov] = read(iv)
                return

        out = eqn.outvars[0]
        shape = tuple(out.aval.shape)
        dtype = _dtype_str(out.aval)
        operands = tuple(read(v) for v in eqn.invars)

        if len(eqn.outvars) > 1:
            _emit_custom(eqn, operands)
            return

        if prim in _EW_PRIMS:
            nm = fresh(_EW_PRIMS[prim])
            g.add(OpNode(nm, OpKind.ELEMENTWISE, shape, dtype, operands,
                         {"op": _EW_PRIMS[prim]}))
        elif prim == "integer_pow":
            p = eqn.params["y"]
            if p == 2:
                nm = fresh("square")
                g.add(OpNode(nm, OpKind.ELEMENTWISE, shape, dtype, operands,
                             {"op": "square"}))
            else:
                # replay integer_pow exactly: lowering to pow(x, float(p))
                # computes exp(p*log x) — a different rounding (and NaN for
                # negative bases) than XLA's repeated-multiply
                nm = fresh("ipow")
                g.add(OpNode(nm, OpKind.ELEMENTWISE, shape, dtype, operands,
                             {"op": "integer_pow", "y": int(p)}))
        elif prim == "select_n":
            nm = fresh("select")
            # lax.select_n(pred, on_false, on_true) -> where(pred, on_true, on_false)
            pred, *cases = operands
            if len(cases) != 2:
                _emit_custom(eqn, operands); return
            g.add(OpNode(nm, OpKind.ELEMENTWISE, shape, dtype,
                         (pred, cases[1], cases[0]), {"op": "select"}))
        elif prim == "convert_element_type":
            nm = fresh("convert")
            g.add(OpNode(nm, OpKind.ELEMENTWISE, shape, dtype, operands,
                         {"op": "convert"}))
        elif prim == "broadcast_in_dim":
            nm = fresh("bcast")
            g.add(OpNode(nm, OpKind.BROADCAST, shape, dtype, operands,
                         {"bcast_dims": tuple(eqn.params["broadcast_dimensions"])}))
        elif prim in ("reshape", "squeeze", "expand_dims"):
            nm = fresh("reshape")
            g.add(OpNode(nm, OpKind.RESHAPE, shape, dtype, operands))
        elif prim == "slice" and not eqn.params.get("strides"):
            nm = fresh("slice")
            g.add(OpNode(nm, OpKind.SLICE, shape, dtype, operands,
                         {"starts": tuple(eqn.params["start_indices"]),
                          "limits": tuple(eqn.params["limit_indices"]),
                          "strides": None}))
        elif prim == "transpose":
            nm = fresh("transpose")
            g.add(OpNode(nm, OpKind.TRANSPOSE, shape, dtype, operands,
                         {"perm": tuple(eqn.params["permutation"])}))
        elif prim in _REDUCE_PRIMS:
            nm = fresh(f"reduce_{_REDUCE_PRIMS[prim]}")
            in_rank = len(eqn.invars[0].aval.shape)
            g.add(OpNode(nm, OpKind.REDUCTION, shape, dtype, operands,
                         {"op": _REDUCE_PRIMS[prim],
                          "axes": tuple(eqn.params["axes"]),
                          "in_rank": in_rank, "keepdims": False}))
        elif prim == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            kind = OpKind.BATCHED_GEMM if lb else OpKind.GEMM
            nm = fresh("dot")
            # the jaxpr's accumulation request must survive into the IR:
            # dropping it made the executors re-derive accumulation dtype
            # from the (possibly bf16) output dtype — see eval_node
            pref = eqn.params.get("preferred_element_type")
            g.add(OpNode(nm, kind, shape, dtype, operands,
                         {"contract": (tuple(lc), tuple(rc)),
                          "batch": (tuple(lb), tuple(rb)),
                          "preferred": None if pref is None
                          else str(np.dtype(pref))}))
        elif prim in _SCATTER_PRIMS and len(eqn.outvars) == 1:
            params = dict(eqn.params)

            def run_scatter(*vals, _prim=eqn.primitive, _params=params):
                return _prim.bind(*vals, **_params)

            nm = fresh(f"scatter_{prim.split('-')[-1]}")
            g.add(OpNode(nm, OpKind.SCATTER, shape, dtype, operands,
                         {"prim": prim, "params_sig": _stable_params_sig(params),
                          "eval_fn": run_scatter}))
        elif prim == "stop_gradient" or prim == "copy":
            env[out] = operands[0]
            return
        else:
            _emit_custom(eqn, operands)
            return
        env[out] = nm

    def _emit_custom(eqn, operands):
        """Opaque but executable node (one per output)."""
        prim = eqn.primitive
        params = dict(eqn.params)

        unwrap = prim.multiple_results and len(eqn.outvars) == 1

        def run(*vals, _prim=prim, _params=params, _unwrap=unwrap):
            res = _prim.bind(*vals, **_params)
            # a multiple_results primitive with ONE outvar (e.g. a scan whose
            # carry is its only output) binds to a 1-element list
            if _unwrap:
                (res,) = res
            return res

        psig = _stable_params_sig(params)
        extra = {}
        if prim.name == "pallas_call":
            # the kernel-body function name identifies WHICH Pallas kernel
            # this is; the kernel registry keys on it, and making
            # it an attr (not just params_sig type-name soup) also makes
            # kernel identity visible to cache signatures
            body = getattr(params.get("jaxpr"), "debug_info", None)
            tag = params.get("name") or getattr(body, "func_name", None)
            if tag:
                extra["kernel"] = str(tag)
        if len(eqn.outvars) == 1:
            out = eqn.outvars[0]
            nm = fresh(f"custom_{prim.name}")
            g.add(OpNode(nm, OpKind.CUSTOM, tuple(out.aval.shape),
                         _dtype_str(out.aval), operands,
                         {"prim": prim.name, "params_sig": psig,
                          "eval_fn": run, **extra}))
            env[out] = nm
        else:
            base = fresh(f"custom_{prim.name}")
            g.add(OpNode(base, OpKind.CUSTOM, (), "float32", operands,
                         {"prim": prim.name, "params_sig": psig,
                          "eval_fn": run, "multi": True, **extra}))
            for i, out in enumerate(eqn.outvars):
                nm = f"{base}.o{i}"
                g.add(OpNode(nm, OpKind.CUSTOM, tuple(out.aval.shape),
                             _dtype_str(out.aval), (base,),
                             {"prim": prim.name, "project": i, **extra}))
                env[out] = nm

    for eqn in closed.jaxpr.eqns:
        emit_eqn(eqn)

    outputs = []
    for v in closed.jaxpr.outvars:
        outputs.append(read(v))
    g.mark_output(*outputs)
    _fold_widening_converts(g)
    g.validate()
    return g, input_names


def _is_float(dtype: str) -> bool:
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def _fold_widening_converts(g: Graph) -> None:
    """Mirror XLA's ``convert_f32(dot_bf16) -> dot_f32`` simplification.

    Under jit, a dot whose value is consumed only by converts to a *wider*
    float type never materializes the narrow intermediate — XLA computes the
    dot at the wide type directly.  The op-by-op executors replay the graph
    literally, rounding to the narrow dtype between the dot and the convert:
    one bf16 ulp of divergence on every logit (the stitched-executor "logit
    wobble").  Widening the dot's declared dtype here (the converts become
    value-preserving no-ops) keeps every executor bitwise-consistent with
    jit.  Dots that are graph outputs keep their spelled dtype.  XLA applies
    the rewrite even when the jaxpr pins ``preferred_element_type`` to the
    narrow dtype (jnp.matmul does), so a narrow ``preferred`` is widened
    along with the output; an already-wide ``preferred`` needs no fold."""
    for node in g.nodes.values():
        if node.kind not in (OpKind.GEMM, OpKind.BATCHED_GEMM):
            continue
        if node.name in g.outputs or not _is_float(node.dtype):
            continue
        pref = node.attrs.get("preferred")
        if pref is not None and np.dtype(pref).itemsize > np.dtype(node.dtype).itemsize:
            continue
        users = g.users(node.name)
        if not users:
            continue
        widths = []
        for u in users:
            un = g[u]
            if (un.kind is not OpKind.ELEMENTWISE
                    or un.attrs.get("op") != "convert"
                    or not _is_float(un.dtype)
                    or np.dtype(un.dtype).itemsize <= np.dtype(node.dtype).itemsize):
                break
            widths.append(un.dtype)
        else:
            wide = max(widths, key=lambda d: np.dtype(d).itemsize)
            node.dtype = wide
            node.attrs["preferred"] = wide
