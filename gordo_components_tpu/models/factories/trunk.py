"""A decoder *trunk* shared by every machine of a bank, with per-machine
projections in front of it and behind it.

The trunk is a stack of pre-norm decoder layers, each a grouped-query
attention over an indexer's selection of keys (``ops/sparse_attention.py``)
followed by a routed expert layer (``ops/moe.py``). It has no tokens: a
machine's scaled sensor rows enter through that machine's own linear
projection ``tags -> hidden`` (as a multimodal decoder takes continuous
features in place of embedding rows) and leave through its own linear head
``hidden -> tags``. A request's rows are ONE causal sequence.

Parameters therefore split in two:

- the **trunk** (``init_trunk``; bfloat16 matrices, float32 norm scales):
  ``{"layers": [layer, ...], "final_norm": (D,)}``, a layer being
  ``attn_norm`` (D,), ``wq`` (D, H*d), ``wk``/``wv`` (D, G*d), ``q_norm``/
  ``k_norm`` (d,), ``wo`` (H*d, D), the indexer's ``idx_wq`` (D, J*dI),
  ``idx_wk`` (D, dI), ``idx_k_scale``/``idx_k_bias`` (dI,), ``idx_ww``
  (D, J), then ``mlp_norm`` (D,), ``router`` (D, E), ``gate``/``up``
  (E, D, I), ``down`` (E, I, D). Held once, whatever the number of machines.
- the **member** (``init_member``; float32): ``{"in_proj": {"kernel"
  (F, D), "bias" (D,)}, "head": {"kernel" (D, F), "bias" (F,)}}``.

Matmuls inside the trunk take bfloat16 operands and accumulate in float32;
norm statistics, the router, indexer scores, softmaxes and the residual
stream are float32. The member's projections are float32 at the platform's
default matmul precision, like every other member of the zoo.
"""

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from gordo_components_tpu.models.register import register_model_builder
from gordo_components_tpu.ops.moe import expert_layer
from gordo_components_tpu.ops.sparse_attention import WITNESS_STRIDE, rope, select_and_attend

F32, BF16 = jnp.float32, jnp.bfloat16


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps=1e-6):
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _mm(x, w):
    return jnp.dot(x.astype(BF16), w.astype(BF16), preferred_element_type=F32)


@dataclass(frozen=True)
class SparseMoEDecoder:
    """The sizes of one trunk and the pure functions over its two
    parameter trees. Key names are the published config's."""

    n_features: int
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_rope_dim: int = 32
    indexer_topk: int = 2048
    chunk_size: int = 512

    # ------------------------------------------------------------ shapes

    def layer_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, H, G, d = (self.hidden_size, self.num_attention_heads,
                      self.num_key_value_heads, self.head_dim)
        J, dI = self.indexer_num_heads, self.indexer_head_dim
        E, I = self.num_experts, self.moe_intermediate_size
        return {
            "attn_norm": (D,), "wq": (D, H * d), "wk": (D, G * d), "wv": (D, G * d),
            "q_norm": (d,), "k_norm": (d,), "wo": (H * d, D),
            "idx_wq": (D, J * dI), "idx_wk": (D, dI), "idx_k_scale": (dI,),
            "idx_k_bias": (dI,), "idx_ww": (D, J),
            "mlp_norm": (D,), "router": (D, E),
            "gate": (E, D, I), "up": (E, D, I), "down": (E, I, D),
        }

    def member_shapes(self) -> Dict[str, Dict[str, Tuple[int, ...]]]:
        F, D = self.n_features, self.hidden_size
        return {"in_proj": {"kernel": (F, D), "bias": (D,)},
                "head": {"kernel": (D, F), "bias": (F,)}}

    def active_params_per_row(self) -> int:
        """Trunk parameters one row's forward multiplies by (its
        ``num_experts_per_tok`` experts, not all of them) plus the
        member's two projections."""
        shapes = self.layer_shapes()
        size = lambda name: math.prod(shapes[name])
        dense = sum(size(n) for n in ("wq", "wk", "wv", "wo", "idx_wq", "idx_wk", "idx_ww", "router"))
        expert = 3 * self.hidden_size * self.moe_intermediate_size
        return (self.num_hidden_layers * (dense + self.num_experts_per_tok * expert)
                + 2 * self.n_features * self.hidden_size)

    def padded_rows(self, rows: int) -> int:
        """A request is never cut: it is padded to whole chunks."""
        return -(-int(rows) // self.chunk_size) * self.chunk_size

    def program_bytes(self, batch: int, rows: int) -> int:
        """Device bytes ``layer`` needs beside its arguments for ``batch``
        requests of ``rows`` padded rows, counted at its two widest points:
        the expert layer's (row, expert) pairs, which go with every row of
        the batch (sorted rows in bfloat16, gate and up, then down's output,
        float32), and one request's selection (requests attend one after
        another: its mask twice over, a chunk's indexer dots in float32);
        plus the residual stream and the projections. What bounds a bank's
        batch. The TPU compiler's own analysis of the program reads 1.36
        and 2.86 GB at 1 and 2 requests of 10 240 rows at the published
        sizes (``tests/test_tpu_compile.py``); this count reads 1.3 times
        that."""
        D, I, k = self.hidden_size, self.moe_intermediate_size, self.num_experts_per_tok
        pairs = batch * rows * k * (2 * D + 8 * I + 4 * D)
        selection = 2 * rows * rows + 4 * self.chunk_size * rows * self.indexer_num_heads
        return int(max(pairs, selection) + batch * rows * 16 * D)

    def forward_flops_per_row(self, context_rows: int) -> float:
        """Forward FLOPs of one row of a ``context_rows``-row request,
        averaged over its positions: 2 a multiply-add of the parameters a
        row meets (its ``num_experts_per_tok`` experts, not all), the
        attention over the keys it selected, the indexer's scores over the
        keys it could see."""
        n, topk = int(context_rows), self.indexer_topk
        selected = sum(min(t + 1, topk) for t in range(n)) / n
        visible = (n + 1) / 2.0
        attend = 4.0 * self.num_attention_heads * self.head_dim * selected
        index = 2.0 * self.indexer_num_heads * self.indexer_head_dim * visible
        return 2.0 * self.active_params_per_row() + self.num_hidden_layers * (attend + index)

    # -------------------------------------------------------------- init

    @staticmethod
    def _uniform(key, shape, fan_in, dtype):
        limit = (3.0 / fan_in) ** 0.5  # variance 1/fan_in
        return jax.random.uniform(key, shape, F32, -limit, limit).astype(dtype)

    def init_trunk(self, key) -> Dict[str, Any]:
        """Random trunk, variance 1/fan_in, norms at one; leaf by leaf."""
        layers = []
        for layer_key in jax.random.split(key, self.num_hidden_layers):
            layer = {}
            shapes = self.layer_shapes()
            for leaf_key, (name, shape) in zip(jax.random.split(layer_key, len(shapes)), shapes.items()):
                if name.endswith(("_norm", "_scale")):
                    layer[name] = jnp.ones(shape, F32)
                elif name.endswith("_bias"):
                    layer[name] = jnp.zeros(shape, F32)
                else:
                    layer[name] = self._uniform(leaf_key, shape, shape[-2], BF16)
            layers.append(layer)
        return {"layers": layers, "final_norm": jnp.ones((self.hidden_size,), F32)}

    def init_member(self, key) -> Dict[str, Any]:
        k_in, k_out = jax.random.split(key)
        shapes = self.member_shapes()
        return {
            "in_proj": {
                "kernel": self._uniform(k_in, shapes["in_proj"]["kernel"], self.n_features, F32),
                "bias": jnp.zeros(shapes["in_proj"]["bias"], F32),
            },
            "head": {
                "kernel": self._uniform(k_out, shapes["head"]["kernel"], self.hidden_size, F32),
                "bias": jnp.zeros(shapes["head"]["bias"], F32),
            },
        }

    # ----------------------------------------------------------- forward

    def embed(self, in_proj, xs):
        """The B machines' input projections: scaled rows (B, T, F) ->
        the trunk's residual stream (B, T, D)."""
        with jax.named_scope("member/in_proj"):
            return jnp.einsum("btf,bfd->btd", xs, in_proj["kernel"]) + in_proj["bias"][:, None, :]

    def layer(self, w, x, n_valid, interpret: bool = False):
        """One decoder layer over the B x T rows of ``x`` (B, T, D), T a
        multiple of ``chunk_size``; ``n_valid`` (B,): rows beyond it are
        padding. Every layer has the same shapes, so a caller that jits
        this compiles it once whatever the depth.

        Returns the next ``x`` and what the layer observed: ``experts``
        (B, T, top_k) uint8, ``witness`` (B, T // stride, T // 8) uint8
        (``ops/sparse_attention.py``), ``expert_tokens`` (E,) and
        ``selections`` (B,) int32."""
        B, T, D = x.shape
        H, G, d = self.num_attention_heads, self.num_key_value_heads, self.head_dim
        J, dI = self.indexer_num_heads, self.indexer_head_dim
        eps = self.rms_norm_eps
        positions = jnp.arange(T)
        valid = positions[None, :] < n_valid[:, None]
        with jax.named_scope("trunk/project"):
            h = _rmsnorm(x, w["attn_norm"], eps)
            q = _rmsnorm(_mm(h, w["wq"]).reshape(B, T, H, d), w["q_norm"], eps)
            k = _rmsnorm(_mm(h, w["wk"]).reshape(B, T, G, d), w["k_norm"], eps)
            v = _mm(h, w["wv"]).reshape(B, T, G, d)
            q, k = rope(q, positions, self.rope_theta), rope(k, positions, self.rope_theta)
            qi = rope(_mm(h, w["idx_wq"]).reshape(B, T, J, dI), positions,
                      self.rope_theta, self.indexer_rope_dim)
            ki = _layernorm(_mm(h, w["idx_wk"]), w["idx_k_scale"], w["idx_k_bias"])
            ki = rope(ki[:, :, None, :], positions, self.rope_theta, self.indexer_rope_dim)[:, :, 0]
            wi = _mm(h, w["idx_ww"])

        def attend(args):
            return select_and_attend(
                *args, topk=self.indexer_topk, chunk=self.chunk_size, interpret=interpret
            )

        # one request at a time: a request's score tiles are what bounds
        # the program's memory, and requests share no keys
        attn, selections, witness = jax.lax.map(attend, (q, k, v, qi, ki, wi, n_valid))
        with jax.named_scope("trunk/project"):
            x = x + _mm(attn.reshape(B, T, H * d), w["wo"])
        with jax.named_scope("trunk/route"):
            h = _rmsnorm(x, w["mlp_norm"], eps).reshape(B * T, D)
        y, experts, tokens = expert_layer(
            h, w, self.num_experts_per_tok, valid.reshape(-1), interpret
        )
        return x + y.reshape(B, T, D), {
            "experts": experts.reshape(B, T, -1).astype(jnp.uint8), "witness": witness,
            "expert_tokens": tokens, "selections": selections,
        }

    def head(self, final_norm, head, x):
        """The trunk's final norm, then the B machines' heads: (B, T, F);
        output row i is the forecast of input row i + 1. ``head=None``:
        the normed state (B, T, D) the heads read."""
        x = _rmsnorm(x, final_norm, self.rms_norm_eps)
        if head is None:
            return x
        with jax.named_scope("member/head"):
            return jnp.einsum("btd,bdf->btf", x, head["kernel"]) + head["bias"][:, None, :]

    def apply(self, trunk, member, xs, n_valid, interpret: bool = False):
        """The whole model: ``embed``, every ``layer``, ``head``. Returns
        ``(out, observed)``: (B, T, F), or the normed state (B, T, D)
        where ``member`` has no head yet, and the layers' observations
        stacked (layers, ...)."""
        x = self.embed(member["in_proj"], xs)
        observed = []
        for w in trunk["layers"]:
            x, seen = self.layer(w, x, n_valid, interpret)
            observed.append(seen)
        out = self.head(trunk["final_norm"], member.get("head"), x)
        return out, jax.tree.map(lambda *a: jnp.stack(a), *observed)

    def witness_stride(self) -> int:
        return min(WITNESS_STRIDE, self.chunk_size)


@register_model_builder(type="TrunkForecast")
def sparse_moe_decoder(n_features: int, compute_dtype: str = "float32", **sizes) -> SparseMoEDecoder:
    """Decoder trunk of sparse-attention + routed-expert layers; ``sizes``
    are the published config's keys (``SparseMoEDecoder``)."""
    if compute_dtype != "float32":
        raise ValueError("the trunk fixes its own dtypes (bfloat16 operands, float32 accumulation)")
    return SparseMoEDecoder(n_features=int(n_features), **sizes)
