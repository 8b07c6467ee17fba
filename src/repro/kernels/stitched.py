"""Generic stitched-kernel emitter — paper §5 mapped to Pallas/TPU.

Given a :class:`FusionPattern` and an implementation :class:`Template`, emit
ONE ``pl.pallas_call`` computing the whole pattern.  The four composition
mechanisms of the paper map as:

* kernel packing       -> independent member ops share the kernel's grid and
                          write separate output refs (their loops are fused);
* thread composition   -> member chains evaluated value-to-value inside the
                          body; intermediates are VREG-resident jnp values;
* warp composition     -> row reductions computed on the VPU tile
                          (``jnp.sum/max(axis=-1)``) feeding dependent
                          elementwise ops in the same body;
* block composition    -> intermediates the template marks ``S`` are routed
                          through explicit VMEM ``scratch_shapes`` refs;
                          column/scalar reductions and row-contracting GEMMs
                          accumulate across sequential grid steps into their
                          output ref (TPU grids are sequential, so
                          cross-block accumulation is well-defined — the TPU
                          analogue of the paper's independent parallel loops
                          inside one kernel).

Supported pattern class — *row-parallel patterns*: there is a leading "row"
dimension R such that every member op either works row-locally (elementwise,
row broadcast/reduction, batched GEMM with batch=rows, gather from a
row-invariant table) or is an explicit cross-row accumulator (column/scalar
reduction, GEMM contracting over R).  The paper's layout constraint (§5.3:
"shared space accessed within a single thread block context") reappears
verbatim: a cross-row accumulator may feed other members only when the whole
row space fits in one block (grid == 1).

The kernel runs in Pallas's interpreter on the CPU and compiles through
Mosaic on a TPU (:func:`repro.kernels.interpret_mode`).  BlockSpecs are
written for TPU VMEM tiling (row-block x full minor dims, sublane multiples
of 8 preferred by the tuner); :func:`block_bytes` is what those blocks hold
in VMEM, which the cost model counts against the chip's on-chip budget.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.codegen import EW_OPS, canonical_dtype, eval_node
from repro.core.cost import vmem_tile_bytes
from repro.core.ir import Graph, OpKind, OpNode
from repro.core.pattern import FusionPattern
from repro.core.templates import Template

__all__ = ["StitchAnalysis", "analyze_pattern", "block_bytes",
           "build_stitched_callable", "StitchInfeasible"]


class StitchInfeasible(Exception):
    """Pattern not in the emitter's supported class (caller falls back to the
    fused-jnp path; the tuner scores the template negative)."""


ROW = "row"          # leading dim == R, sliced per block
INV = "invariant"    # no row dim; fully resident per block
ACC = "accumulator"  # produced by cross-row accumulation over grid steps


@dataclass
class StitchAnalysis:
    rows: int                               # R
    roles: dict[str, str]                   # node -> ROW | INV | ACC
    acc_init: dict[str, tuple[str, float]]  # acc node -> (combine, init value)
    feasible_blocks: list[int]              # row-block sizes that divide R
    single_block: bool = False              # an ACC feeds members (grid must be 1)


def _role_of_input(node: OpNode, rows: int) -> str:
    return ROW if node.shape and node.shape[0] == rows else INV


def analyze_pattern(p: FusionPattern) -> StitchAnalysis:
    """Try candidate row dimensions in priority order (output leading dims
    first — outputs define the kernel's write parallelism — then input
    leading dims); the first candidate under which every member op is
    row-local or an accumulator wins."""
    g = p.graph
    outs = p.external_outputs
    if not outs:
        raise StitchInfeasible("pattern has no outputs")

    cands: dict[int, float] = {}
    for n in outs:
        shp = g[n].shape
        if shp and shp[0] > 1:  # rows=1 is degenerate (everything aliases)
            cands[shp[0]] = cands.get(shp[0], 0) + 1000.0
    for n in p.external_inputs:
        shp = g[n].shape
        if shp and shp[0] > 1:
            cands[shp[0]] = cands.get(shp[0], 0) + 1.0
    if not cands:
        raise StitchInfeasible("no shaped tensors")
    order = sorted(cands, key=lambda k: (-cands[k], -k))
    # inputs consumed ONLY as gemm rhs / gather tables are weights: even when
    # their leading dim coincides with R (square matrices), they are
    # row-invariant.  Tried as a fallback classification.
    def _is_weight_use(user: str, name: str) -> bool:
        node = g[user]
        if node.kind in (OpKind.GEMM, OpKind.BATCHED_GEMM):
            return len(node.operands) > 1 and node.operands[1] == name
        if node.kind is OpKind.GATHER:
            return node.operands[0] == name
        if node.kind is OpKind.BROADCAST:
            # operand axis 0 maps to a non-leading target axis -> per-channel
            # weight broadcast (gamma etc.), not a per-row tensor
            dims = tuple(node.attrs.get("bcast_dims", ()))
            return bool(dims) and dims[0] != 0
        return False

    rhs_only: set[str] = set()
    for name in p.external_inputs:
        users = [u for u in g.users(name) if u in p.members]
        if users and all(_is_weight_use(u, name) for u in users):
            rhs_only.add(name)
    last_err: StitchInfeasible | None = None
    for rows in order:
        for force_inv in ((frozenset(), frozenset(rhs_only))
                          if rhs_only else (frozenset(),)):
            try:
                return _analyze_with_rows(p, rows, force_inv)
            except StitchInfeasible as e:
                last_err = e
    raise last_err if last_err is not None else StitchInfeasible("no viable rows")


def _analyze_with_rows(p: FusionPattern, rows: int,
                       force_inv: frozenset[str] = frozenset()) -> StitchAnalysis:
    g = p.graph

    roles: dict[str, str] = {}
    acc_init: dict[str, tuple[str, float]] = {}
    for name in p.external_inputs:
        roles[name] = INV if name in force_inv else _role_of_input(g[name], rows)

    topo_members = [n.name for n in p.nodes if not n.is_source()]
    single_block = False
    for name in topo_members:
        node = g[name]
        ops = node.operands
        op_roles = [roles.get(o) for o in ops]
        if any(r is None for r in op_roles):
            # operand outside pattern and not an external input -> impossible
            raise StitchInfeasible(f"unrooted operand of {name}")
        if any(r == ACC for r in op_roles):
            # §5.3 layout constraint: an accumulator's value only exists once
            # the whole row space has been visited, so a member may consume it
            # only when the entire row space is one block (grid == 1) — then
            # the fully-reduced value is live in the body and behaves like a
            # row-invariant operand.
            single_block = True
            op_roles = [INV if r == ACC else r for r in op_roles]

        k = node.kind
        if k is OpKind.ELEMENTWISE:
            # a ROW operand arrives as an (rb, ...) block; any other operand
            # spanning the full row space cannot be combined with it
            # value-to-value (it would need per-block slicing)
            if ROW in op_roles:
                for o, r in zip(ops, op_roles):
                    oshape = g[o].shape
                    if (r == INV and oshape and oshape[0] == rows
                            and roles.get(o) != ACC):
                        raise StitchInfeasible(
                            f"{name} mixes a row block with full-rows operand {o}")
            roles[name] = ROW if ROW in op_roles else INV
        elif k is OpKind.BROADCAST:
            dims = tuple(node.attrs.get("bcast_dims", ()))
            src_shape = g[ops[0]].shape
            if op_roles[0] == ROW:
                # the operand's row axis (its dim 0) must land on the target's
                # leading axis, and the target must keep the row extent
                if (dims and dims[0] == 0 and node.shape
                        and node.shape[0] == rows):
                    roles[name] = ROW
                else:
                    raise StitchInfeasible(
                        f"broadcast {name} moves a row-blocked operand off the row axis")
            elif node.shape and node.shape[0] == rows:
                # target spans rows; sound only if no operand dim carrying
                # real extent maps onto the row axis (pure replication)
                if dims and dims[0] == 0 and src_shape and src_shape[0] != 1:
                    raise StitchInfeasible(
                        f"broadcast {name} needs per-block rows of invariant {ops[0]}")
                roles[name] = ROW
            else:
                roles[name] = INV
        elif k is OpKind.RESHAPE:
            src = g[ops[0]]
            if roles[ops[0]] == ROW:
                if node.shape and node.shape[0] == rows and src.shape and src.shape[0] == rows:
                    roles[name] = ROW      # row-local reshape of trailing dims
                else:
                    raise StitchInfeasible(f"reshape {name} mixes rows")
            else:
                roles[name] = INV
        elif k is OpKind.SLICE:
            starts = node.attrs["starts"]
            src_shape = g[ops[0]].shape
            if roles[ops[0]] == ROW:
                if starts[0] == 0 and node.shape[0] == src_shape[0]:
                    roles[name] = ROW     # trailing-dim slice, row-local
                else:
                    raise StitchInfeasible(f"slice {name} cuts the row axis")
            else:
                roles[name] = INV
        elif k is OpKind.TRANSPOSE:
            perm = tuple(node.attrs["perm"])
            if roles[ops[0]] == ROW:
                if perm and perm[0] == 0:
                    roles[name] = ROW
                else:
                    raise StitchInfeasible(f"transpose {name} moves row axis")
            else:
                roles[name] = INV
        elif k is OpKind.REDUCTION:
            axes = tuple(node.attrs["axes"])
            if roles[ops[0]] == ROW and 0 in axes:
                red = node.attrs.get("op", "sum")
                if red not in ("sum", "max", "min"):
                    raise StitchInfeasible(f"cross-row reduce op {red}")
                roles[name] = ACC
                acc_init[name] = {
                    "sum": ("add", 0.0),
                    "max": ("max", -jnp.inf),
                    "min": ("min", jnp.inf),
                }[red]
            elif roles[ops[0]] == ROW:
                roles[name] = ROW
            else:
                roles[name] = INV
        elif k in (OpKind.GEMM, OpKind.BATCHED_GEMM):
            (lc, rc) = node.attrs["contract"]
            (lb, rb_) = node.attrs.get("batch", ((), ()))
            lrole, rrole = roles[ops[0]], roles[ops[1]]
            if lrole == ROW and rrole == ROW and 0 in lb and 0 in rb_:
                roles[name] = ROW          # batched over rows
            elif lrole == ROW and rrole == INV and 0 not in lc:
                roles[name] = ROW          # (R, k) @ (k, n)
            elif lrole == ROW and rrole == ROW and 0 in lc and 0 in rc:
                roles[name] = ACC          # contract over rows -> accumulate
                acc_init[name] = ("add", 0.0)
            elif lrole == INV and rrole == INV:
                roles[name] = INV
            else:
                raise StitchInfeasible(f"gemm {name} row structure unsupported")
        elif k is OpKind.GATHER:
            trole, irole = roles[ops[0]], roles[ops[1]]
            if trole == INV:
                roles[name] = irole
            else:
                raise StitchInfeasible(f"gather {name} from row-varying table")
        elif k is OpKind.TUPLE:
            roles[name] = INV
        else:
            raise StitchInfeasible(f"unsupported kind {k} in stitched kernel")

    blocks = [b for b in (8, 16, 32, 64, 128, 256, 512, rows) if b <= rows and rows % b == 0]
    if single_block:
        blocks = [rows]
    if not blocks:
        blocks = [rows]
    return StitchAnalysis(rows, roles, acc_init, sorted(set(blocks)), single_block)


def _block_shape(shape: tuple[int, ...], role: str, rb: int) -> tuple[int, ...]:
    if role == ROW and shape:
        return (rb,) + shape[1:]
    return shape


def _io_blocks(p: FusionPattern, ana: StitchAnalysis, rb: int):
    """(block shape, dtype) of every kernel input then output, as the
    BlockSpecs of :func:`build_stitched_callable` lay them out: row-role
    tensors are cut to ``rb`` rows, everything else is resident whole
    (scalars as ``(1,)``)."""
    g = p.graph
    for name in list(p.external_inputs) + list(p.external_outputs):
        node = g[name]
        shp = node.shape or (1,)
        yield (_block_shape(shp, ana.roles[name], rb),
               canonical_dtype(node.dtype))


def block_bytes(p: FusionPattern, ana: StitchAnalysis, rb: int) -> int:
    """VMEM bytes of one copy of the kernel's input and output blocks at
    row block ``rb`` (the Pallas pipeline holds two)."""
    return sum(vmem_tile_bytes(shp, jnp.dtype(dt).itemsize)
               for shp, dt in _io_blocks(p, ana, rb))


def _subst_rows(shape: tuple[int, ...], rows: int, rb: int) -> tuple[int, ...]:
    if shape and shape[0] == rows:
        return (rb,) + shape[1:]
    return shape


def _eval_rowlocal(node: OpNode, operands: list, rows: int, rb: int):
    """eval_node, with row-parallel target shapes rewritten R -> rb."""
    k = node.kind
    if k is OpKind.SLICE and node.shape and node.shape[0] == rows:
        starts = list(node.attrs["starts"])
        limits = list(node.attrs["limits"])
        limits[0] = operands[0].shape[0]   # row axis handled by the grid
        return lax.slice(operands[0], starts, limits)
    if k is OpKind.BROADCAST:
        return lax.broadcast_in_dim(
            operands[0], _subst_rows(node.shape, rows, rb), tuple(node.attrs["bcast_dims"])
        )
    if k is OpKind.RESHAPE:
        return jnp.reshape(operands[0], _subst_rows(node.shape, rows, rb))
    return eval_node(node, operands)


def build_stitched_callable(
    p: FusionPattern,
    template: Template | None = None,
    *,
    row_block: int | None = None,
    scratch_ops: Sequence[str] = (),
) -> Callable[..., tuple]:
    """Emit the fused kernel.  Returns ``f(*external_inputs) -> tuple(outputs)``
    (input/output order = ``p.external_inputs`` / ``p.external_outputs``)."""
    g = p.graph
    ana = analyze_pattern(p)
    rows = ana.rows

    if template is not None:
        scratch_ops = tuple(template.scratch_ops)
        for s in template:
            for a in s.attrs:
                for lvl in a.levels:
                    if lvl.kind == "GRID" and lvl.factor:
                        row_block = lvl.factor
    rb = row_block or ana.feasible_blocks[0]
    if rb not in ana.feasible_blocks:
        # snap to the largest feasible block <= requested
        rb = max((b for b in ana.feasible_blocks if b <= rb), default=ana.feasible_blocks[0])
    grid = rows // rb

    ins = list(p.external_inputs)
    outs = list(p.external_outputs)
    roles = ana.roles
    member_topo = [n for n in p.nodes if not n.is_source()]
    scratch_set = {s for s in scratch_ops if s in p.members and roles.get(s) == ROW}

    specs = []
    for name, (bs, _) in zip(ins + outs, _io_blocks(p, ana, rb)):
        if roles[name] == ROW:
            specs.append(pl.BlockSpec(
                bs, lambda i, _n=len(bs): (i,) + (0,) * (_n - 1)))
        else:  # INV or ACC: the whole tensor every step
            specs.append(pl.BlockSpec(bs, lambda i, _n=len(bs): (0,) * _n))
    in_specs, out_specs = specs[:len(ins)], specs[len(ins):]
    out_shapes = [jax.ShapeDtypeStruct(g[name].shape or (1,),
                                       canonical_dtype(g[name].dtype))
                  for name in outs]

    scratch_order = sorted(scratch_set)
    scratch_shapes = [
        pltpu.VMEM(_block_shape(g[name].shape, ROW, rb),
                   canonical_dtype(g[name].dtype))
        for name in scratch_order]

    n_in, n_out = len(ins), len(outs)

    def body(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + n_out]
        scr_refs = refs[n_in + n_out:]
        scr_map = dict(zip(scratch_order, scr_refs))
        pid = pl.program_id(0)

        env: dict[str, jax.Array] = {}
        for name, ref in zip(ins, in_refs):
            val = ref[...]
            if not g[name].shape:  # scalars arrive as (1,) blocks
                val = val.reshape(())
            env[name] = val

        for node in member_topo:
            name = node.name
            vals = [env[o] for o in node.operands]
            role = roles[name]
            if role == ACC:
                # partial contribution of this row block
                partial_val = eval_node(node, vals)
                combine, init = ana.acc_init[name]
                if grid == 1:
                    # fully reduced in one step: the value is live in the body
                    # and may feed other members (block composition, §5.3)
                    env[name] = partial_val
                    if name in outs:
                        oref = out_refs[outs.index(name)]
                        oref[...] = partial_val.reshape(oref.shape)
                else:
                    oref = out_refs[outs.index(name)]

                    @pl.when(pid == 0)
                    def _init(oref=oref, init=init):
                        oref[...] = jnp.full(oref.shape, init, oref.dtype)
                    cur = oref[...]
                    upd = {
                        "add": lambda a, b: a + b,
                        "max": jnp.maximum,
                        "min": jnp.minimum,
                    }[combine](cur, partial_val.reshape(oref.shape))
                    oref[...] = upd
                    env[name] = None  # not consumable (layout constraint)
                continue
            val = _eval_rowlocal(node, vals, rows, rb)
            if name in scr_map:                 # block composition via VMEM
                scr_map[name][...] = val
                val = scr_map[name][...]
            env[name] = val

        for name, oref in zip(outs, out_refs):
            if roles[name] == ACC:
                continue  # written by the accumulator path above
            val = env[name]
            oref[...] = val.reshape(oref.shape)

    call = pl.pallas_call(
        body,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch_shapes,
        interpret=kernels.interpret_mode(),
    )

    def run(*inputs):
        prepared = []
        for name, x in zip(ins, inputs):
            # canonicalized: the graph dtype stays authoritative without ever
            # requesting an x64 width this runtime doesn't provide
            x = jnp.asarray(x, dtype=canonical_dtype(g[name].dtype))
            if not g[name].shape:
                x = x.reshape(1)
            prepared.append(x)
        res = call(*prepared)
        if not isinstance(res, (tuple, list)):
            res = (res,)
        fixed = []
        for name, r in zip(outs, res):
            fixed.append(r.reshape(g[name].shape))
        return tuple(fixed)

    return run
