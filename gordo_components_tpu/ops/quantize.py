"""Low-precision storage for the bank's stacked weights.

The HBM-resident :class:`~gordo_components_tpu.server.bank.ModelBank`
stacks every bucket's params into one pytree with a leading *member*
axis. At fleet scale those stacks bound models-per-chip: fp32 weights
are the single largest HBM tenant, and the scoring math never needs
them at full precision — compute happens in fp32 *after* a per-member
gather, so the stored stack only has to round-trip one member's worth
of weights per request (PAPERS.md #1: quantized serving is where TPU
stacks earn their margin).

Two storage modes below fp32 (``GORDO_BANK_DTYPE``):

- **bfloat16** — same exponent range as fp32, 8-bit mantissa: a plain
  ``astype`` halves the stack with a worst-case ~2^-9 relative rounding
  error per weight. No extra state.
- **int8** — per-member-per-tensor absmax scaling: each stacked leaf
  ``(M, ...)`` stores int8 codes plus an ``(M, 1, ...)`` fp32 scale
  (``absmax / 127`` over that member's tensor), ~4x smaller than fp32.
  One member's outlier cannot flatten another member's resolution
  because scales never cross the member axis.

Dequantization happens INSIDE the compiled scoring program, after the
batch's members are selected (:func:`dequantize_params`): HBM holds the small
representation, VMEM/compute sees fp32. The int8 container
(:class:`QuantizedLeaf`) is a registered pytree node so the bank's
existing machinery — ``device_put`` per leaf, ``shard_map`` in-specs,
the per-member slices of ``server/bank.py::_select_members`` — works on
quantized stacks unchanged: both children carry the leading member axis.
"""

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BANK_DTYPES",
    "QuantizedLeaf",
    "dequantize_params",
    "normalize_bank_dtype",
    "quantize_stacked",
    "tree_weight_bytes",
]

# accepted GORDO_BANK_DTYPE values (aliases normalized below)
BANK_DTYPES = ("float32", "bfloat16", "int8")
_ALIASES = {
    "fp32": "float32", "f32": "float32",
    "bf16": "bfloat16",
    "i8": "int8",
}


def normalize_bank_dtype(value: str) -> str:
    """Canonical bank dtype from an env/config string (raises on junk —
    a typo'd fleet-wide knob must fail loudly at startup, not silently
    serve fp32)."""
    canon = _ALIASES.get(str(value).strip().lower(), str(value).strip().lower())
    if canon not in BANK_DTYPES:
        raise ValueError(
            f"bank dtype must be one of {'|'.join(BANK_DTYPES)}, got {value!r}"
        )
    return canon


@jax.tree_util.register_pytree_node_class
class QuantizedLeaf:
    """Int8 codes + broadcast-ready fp32 scale for one stacked tensor.

    ``values``: ``(M, ...)`` int8; ``scale``: ``(M, 1, ...)`` fp32 (same
    rank, so ``values * scale`` broadcasts after any prefix of leading
    axes is gathered away). Registered as a pytree node: tree maps, jit
    tracing, ``device_put`` sharding, and shard_map specs all descend
    into the two children transparently.
    """

    __slots__ = ("values", "scale")

    def __init__(self, values: Any, scale: Any):
        self.values = values
        self.scale = scale

    def tree_flatten(self) -> Tuple[Tuple[Any, Any], None]:
        return (self.values, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children) -> "QuantizedLeaf":
        return cls(*children)

    @property
    def nbytes(self) -> int:
        return int(self.values.nbytes) + int(self.scale.nbytes)

    def dequantize(self) -> jnp.ndarray:
        return self.values.astype(jnp.float32) * self.scale

    def __repr__(self) -> str:  # debugging aid, never on a hot path
        return (
            f"QuantizedLeaf(values={getattr(self.values, 'shape', None)}, "
            f"scale={getattr(self.scale, 'shape', None)})"
        )


def _quantize_leaf_int8(leaf: np.ndarray) -> QuantizedLeaf:
    """Per-member symmetric absmax quantization of one stacked leaf."""
    leaf = np.asarray(leaf, np.float32)
    axes = tuple(range(1, leaf.ndim))
    # rank-1 stacked scalars: (M,) -> each member's "tensor" is a scalar,
    # its own absmax
    absmax = np.max(np.abs(leaf), axis=axes, keepdims=True) if axes else np.abs(leaf)
    # an all-zero member tensor quantizes to zeros under ANY scale; 1.0
    # keeps the divide finite without perturbing the codes
    scale = np.where(absmax > 0.0, absmax / 127.0, 1.0).astype(np.float32)
    codes = np.clip(np.rint(leaf / scale), -127, 127).astype(np.int8)
    return QuantizedLeaf(codes, scale)


def _is_quantizable(leaf: Any) -> bool:
    """Only floating weight tensors shrink; integer/bool state (none in
    the current factories, but checkpoints may grow some) passes through
    untouched. jnp's dtype lattice, not numpy's: ml_dtypes extensions
    (bfloat16) are floating here but unknown to ``np.issubdtype``."""
    dtype = getattr(leaf, "dtype", None)
    return dtype is not None and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def quantize_stacked(params: Any, bank_dtype: str) -> Any:
    """Quantize a stacked (leading member axis) params pytree for HBM
    residency. ``float32`` returns the tree unchanged (identity — the
    parity baseline must not even copy)."""
    bank_dtype = normalize_bank_dtype(bank_dtype)
    if bank_dtype == "float32":
        return params
    if bank_dtype == "bfloat16":
        return jax.tree.map(
            lambda a: np.asarray(a).astype(jnp.bfloat16)
            if _is_quantizable(a)
            else a,
            params,
        )
    return jax.tree.map(
        lambda a: _quantize_leaf_int8(a) if _is_quantizable(a) else a,
        params,
        is_leaf=lambda a: isinstance(a, QuantizedLeaf),
    )


def dequantize_params(params: Any) -> Any:
    """fp32 view of a (possibly gathered) quantized pytree — traced
    inside the compiled scoring program, so HBM holds the low-precision
    stack while all compute accumulates in fp32. Identity on fp32 leaves."""

    def _deq(leaf: Any):
        if isinstance(leaf, QuantizedLeaf):
            return leaf.dequantize()
        if _is_quantizable(leaf) and jnp.dtype(leaf.dtype) != jnp.float32:
            return leaf.astype(jnp.float32)
        return leaf

    return jax.tree.map(
        _deq, params, is_leaf=lambda a: isinstance(a, QuantizedLeaf)
    )


def tree_weight_bytes(params: Any) -> int:
    """Host/HBM footprint of a stacked params pytree in bytes
    (QuantizedLeaf children — codes and scales — both count: the scale
    overhead is exactly what keeps int8 below the naive 4x claim)."""
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(params)))
