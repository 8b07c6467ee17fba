"""Registry of the repo's own Pallas kernels, keyed by kernel identity.

The tracer lowers every ``pallas_call`` to an opaque ``OpKind.CUSTOM`` node
carrying an ``eval_fn`` that replays the saved primitive, and tags it with
the kernel-body function name (``attrs["kernel"]``).  Such a node always
partitions fusion: replaying its ``pallas_call`` inside a stitched kernel
would nest one Pallas kernel in another, which the TPU's Mosaic lowering
refuses.  What a registered kernel declares is its compute:

* ``flops`` — an MXU/compute estimate from the operand shapes, so the cost
  model's roofline sees the kernel as compute-bearing rather than free.

Only :mod:`repro.core.ir` is imported here — no Pallas, no jax — so
``core.cost -> kernels.registry`` adds no import cycles and no accelerator
requirements at planning time.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from repro.core.ir import Graph, OpKind, OpNode

__all__ = ["RegisteredKernel", "register", "lookup"]


@dataclass(frozen=True)
class RegisteredKernel:
    """Compiler-facing descriptor of one registered Pallas kernel.

    ``flops`` receives the CUSTOM node and its graph and derives the
    estimate from the *operand* shapes (output shapes are unreliable for
    multi-output kernels, whose base node is shapeless)."""

    name: str
    flops: Callable[[OpNode, Graph], float]


_REGISTRY: dict[str, RegisteredKernel] = {}


def register(desc: RegisteredKernel) -> RegisteredKernel:
    """Install (or replace) a descriptor under ``desc.name``."""
    _REGISTRY[desc.name] = desc
    return desc


def lookup(node: OpNode) -> Optional[RegisteredKernel]:
    """Descriptor for a CUSTOM node whose traced kernel tag is registered.

    Projection nodes (``attrs["project"]``) resolve through the same tag as
    their multi-output base, so callers can ask about either."""
    if node.kind is not OpKind.CUSTOM:
        return None
    tag = node.attrs.get("kernel")
    if not tag:
        return None
    return _REGISTRY.get(tag)


# -- built-in descriptors -----------------------------------------------------
#
# The formulas mirror the actual kernel bodies (kernels/flash_attention.py,
# kernels/router.py, kernels/decode_attention.py,
# kernels/latent_attention.py, the VPU kernels).


def _flash_flops(node: OpNode, g: Graph) -> float:
    q = g[node.operands[0]].shape            # (B, Lq, Hq, Dh)
    kv = g[node.operands[1]].shape           # (B, Lkv, Hkv, Dh)
    if len(q) != 4 or len(kv) != 4:
        return 0.0
    b, lq, hq, dh = q
    lkv = kv[1]
    # QK^T and PV each cost 2*Lq*Lkv*Dh MACs per (batch, head)
    return 4.0 * b * hq * lq * lkv * dh


def _router_flops(node: OpNode, g: Graph) -> float:
    logits = g[node.operands[0]].shape       # (T, E)
    if len(logits) != 2:
        return 0.0
    t, e = logits
    # per row: k iterative max-scans over E plus softmax-ish normalisation
    return float(t * e * 8)


def _decode_attn_flops(node: OpNode, g: Graph) -> float:
    # pallas_call operands: (positions, q^T, k^T, v^T) — see
    # kernels/decode_attention.py; q^T is (B, Hq, 1, Dh), kv^T (B, Hkv, Smax, Dh)
    if len(node.operands) < 3:
        return 0.0
    q = g[node.operands[1]].shape
    kv = g[node.operands[2]].shape
    if len(q) != 4 or len(kv) != 4:
        return 0.0
    b, hq, _, dh = q
    smax = kv[2]
    # one QK^T row and one PV row per (batch, head): 2*Smax*Dh MACs each
    return 4.0 * b * hq * smax * dh


def _paged_decode_flops(node: OpNode, g: Graph) -> float:
    # pallas_call operands: (layer, page_table (B*n,), lengths, q (B, Hq, Dh),
    # k_new, v_new, pool_k (L, P, page, Hkv, Dh), pool_v) — see
    # kernels/decode_attention.py; priced as attention over every slot's
    # whole view (the live length is a runtime value)
    if len(node.operands) < 7:
        return 0.0
    pt = g[node.operands[1]].shape
    q = g[node.operands[3]].shape
    pool = g[node.operands[6]].shape
    if len(pt) != 1 or len(q) != 3 or len(pool) != 5:
        return 0.0
    b, hq, dh = q
    smax = pt[0] // max(b, 1) * pool[2]
    return 4.0 * b * hq * smax * dh


def _paged_latent_flops(node: OpNode, g: Graph) -> float:
    # pallas_call operands: (layer, page_table (B*n,), lengths, q_lat (B, H,
    # rank), q_pe stacked (B, pack*H, lanes), c_new, pe_new, pool_lat (L, P,
    # page, rank), pool_rope) — see kernels/latent_attention.py; priced as
    # attention over every slot's whole view, its rope scored once per
    # lane group
    if len(node.operands) < 8:
        return 0.0
    pt = g[node.operands[1]].shape
    q = g[node.operands[3]].shape
    qb = g[node.operands[4]].shape
    pool = g[node.operands[7]].shape
    if len(pt) != 1 or len(q) != 3 or len(qb) != 3 or len(pool) != 4:
        return 0.0
    b, h, rank = q
    smax = pt[0] // max(b, 1) * pool[2]
    # scores against the latent and the packed rope rows, then p . c
    return 2.0 * b * smax * (2 * h * rank + qb[1] * qb[2])


def _vpu_flops(per_elem: float, operand: int = 0):
    """Memory-bound VPU kernels (norms, rope, GLU): a few ops per element of
    the named operand, no MXU work."""

    def flops(node: OpNode, g: Graph) -> float:
        if len(node.operands) <= operand:
            return 0.0
        return per_elem * float(g[node.operands[operand]].size)

    return flops


register(RegisteredKernel("_flash_kernel", _flash_flops))
register(RegisteredKernel("_router_kernel", _router_flops))
register(RegisteredKernel("_decode_attn_kernel", _decode_attn_flops))
register(RegisteredKernel("_paged_decode_kernel", _paged_decode_flops))
register(RegisteredKernel("_paged_latent_kernel", _paged_latent_flops))
register(RegisteredKernel("_rmsnorm_kernel", _vpu_flops(4.0)))
register(RegisteredKernel("_rmsnorm_residual_kernel", _vpu_flops(5.0)))
register(RegisteredKernel("_layernorm_kernel", _vpu_flops(6.0)))
register(RegisteredKernel("_rope_kernel", _vpu_flops(6.0)))
register(RegisteredKernel("_glu_kernel", _vpu_flops(4.0)))
