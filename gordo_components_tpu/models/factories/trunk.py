"""A decoder *trunk* shared by every machine of a bank, with per-machine
projections in front of it and behind it.

The trunk is a stack of pre-norm decoder layers. It has no tokens: a
machine's scaled sensor rows enter through that machine's own linear
projection ``tags -> hidden`` (as a multimodal decoder takes continuous
features in place of embedding rows) and leave through its own linear head
``hidden -> tags``. A request's rows are ONE causal sequence. Two kinds:

- ``sparse_moe_decoder`` (``SparseMoEDecoder``): every layer a
  grouped-query attention over an indexer's selection of keys
  (``ops/sparse_attention.py``) followed by a routed expert layer
  (``ops/moe.py``), every expert held.
- ``latent_moe_decoder`` (``LatentMoEDecoder``): every layer a multi-head
  latent attention (``ops/latent_attention.py``); the first
  ``first_k_dense_replace`` layers then a dense SwiGLU, the rest a
  group-limited sigmoid router over ``n_routed_experts`` beside a shared
  expert, of which this trunk holds the range ``expert_offset ..
  expert_offset + experts_held`` (one chip's share of a layer divided over
  chips by experts: ``ops/moe.py``).

Parameters split in two:

- the **trunk** (``init_trunk``; bfloat16 matrices, float32 norm scales):
  ``{"layers": [layer, ...], "final_norm": (D,)}``, a layer's leaves being
  its kind's ``layer_shapes``. Held once, whatever the number of machines.
- the **member** (``init_member``; float32): ``{"in_proj": {"kernel"
  (F, D), "bias" (D,)}, "head": {"kernel" (D, F), "bias" (F,)}}``.

Matmuls inside the trunk take bfloat16 operands and accumulate in float32;
norm statistics, the router, indexer scores, softmaxes and the residual
stream are float32. The member's projections are float32 at the platform's
default matmul precision, like every other member of the zoo.
"""

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from gordo_components_tpu.models.register import register_model_builder
from gordo_components_tpu.ops.latent_attention import latent_attention, yarn
from gordo_components_tpu.ops.moe import expert_layer
from gordo_components_tpu.ops.sparse_attention import WITNESS_STRIDE, rope, select_and_attend

F32, BF16 = jnp.float32, jnp.bfloat16
# the routed experts of a ``LatentMoEDecoder`` take the rows in runs of at
# most this many chunks (2 560 rows at 512): a run's (row, expert) pairs,
# held or not, are what bounds the layer program's memory
_CHUNKS_A_RUN = 5


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


def _swiglu(h, gate, up, down):
    return _mm(jax.nn.silu(_mm(h, gate)) * _mm(h, up), down)


@dataclass(frozen=True)
class _Trunk:
    """What every kind of trunk shares: the member's two projections, the
    random init, and the walk through the layers. A kind adds its sizes
    (``hidden_size``, ``num_hidden_layers``, ``rms_norm_eps``,
    ``chunk_size`` among them), ``layer_shapes`` and ``layer``."""

    n_features: int

    def member_shapes(self) -> Dict[str, Dict[str, Tuple[int, ...]]]:
        F, D = self.n_features, self.hidden_size
        return {"in_proj": {"kernel": (F, D), "bias": (D,)},
                "head": {"kernel": (D, F), "bias": (F,)}}

    def padded_rows(self, rows: int) -> int:
        """A request is never cut: it is padded to whole chunks."""
        return -(-int(rows) // self.chunk_size) * self.chunk_size

    # -------------------------------------------------------------- init

    @staticmethod
    def _uniform(key, shape, fan_in, dtype):
        limit = (3.0 / fan_in) ** 0.5  # variance 1/fan_in
        return jax.random.uniform(key, shape, F32, -limit, limit).astype(dtype)

    def init_trunk(self, key) -> Dict[str, Any]:
        """Random trunk, variance 1/fan_in, norms at one; leaf by leaf."""
        layers = []
        for index, layer_key in enumerate(jax.random.split(key, self.num_hidden_layers)):
            layer = {}
            shapes = self.layer_shapes(index)
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
        stacked (layers that observed it, ...)."""
        x = self.embed(member["in_proj"], xs)
        observed = []
        for w in trunk["layers"]:
            x, seen = self.layer(w, x, n_valid, interpret)
            observed.append(seen)
        out = self.head(trunk["final_norm"], member.get("head"), x)
        return out, stack_observed(observed, jnp.stack)


def stack_observed(layers: List[Dict[str, Any]], stack) -> Dict[str, Any]:
    """What the layers observed, each name stacked over the layers that
    observed it (a dense layer routes nothing and observes nothing)."""
    names = dict.fromkeys(name for seen in layers for name in seen)
    return {name: stack([seen[name] for seen in layers if name in seen]) for name in names}


@dataclass(frozen=True)
class SparseMoEDecoder(_Trunk):
    """Indexer-selected grouped-query attention and a routed expert layer
    in every layer, every expert held: the sizes, and the pure functions
    over the two parameter trees. Key names are the published config's.

    A layer's leaves: ``attn_norm`` (D,), ``wq`` (D, H*d), ``wk``/``wv``
    (D, G*d), ``q_norm``/``k_norm`` (d,), ``wo`` (H*d, D), the indexer's
    ``idx_wq`` (D, J*dI), ``idx_wk`` (D, dI), ``idx_k_scale``/
    ``idx_k_bias`` (dI,), ``idx_ww`` (D, J), then ``mlp_norm`` (D,),
    ``router`` (D, E), ``gate``/``up`` (E, D, I), ``down`` (E, I, D)."""

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

    def layer_shapes(self, layer: int = 0) -> Dict[str, Tuple[int, ...]]:
        """Every layer's, whatever ``layer``."""
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

    def nominal_context_rows(self) -> int:
        """The request length a per-row FLOP count is quoted at: five
        times the selection (10 240 rows at top-k 2048)."""
        return 5 * self.indexer_topk

    # ----------------------------------------------------------- forward

    def layer(self, w, x, n_valid, interpret: bool = False):
        """One decoder layer over the B x T rows of ``x`` (B, T, D), T a
        multiple of ``chunk_size``; ``n_valid`` (B,): rows beyond it are
        padding. Every layer of this kind has the same shapes, so a caller
        that jits this compiles it once whatever the depth.

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

    def witness_stride(self) -> int:
        return min(WITNESS_STRIDE, self.chunk_size)


@dataclass(frozen=True)
class LatentMoEDecoder(_Trunk):
    """Multi-head latent attention in every layer; the first
    ``first_k_dense_replace`` layers then a dense SwiGLU, the rest a routed
    expert layer beside a shared expert. Key names are the published
    config's (the DeepSeek-V3 family's), but for the share:
    ``n_routed_experts`` is the router's width, and the trunk holds experts
    ``expert_offset .. expert_offset + experts_held`` of every routed layer
    (``experts_held=None``: all of them).

    A layer's leaves (``layer_shapes``): ``attn_norm`` (D,), ``q_a``
    (D, q_lora_rank), ``q_a_norm``, ``q_b`` (q_lora_rank, H*(nope+rope)),
    ``kv_a`` (D, kv_lora_rank+rope), ``kv_a_norm``, ``kv_b`` (kv_lora_rank,
    H*(nope+v)), ``wo`` (H*v, D), ``mlp_norm`` (D,); then, a dense layer:
    ``gate``/``up`` (D, intermediate_size), ``down``; a routed layer:
    ``router`` (D, n_routed_experts), ``gate``/``up`` (held, D, I),
    ``down`` (held, I, D), ``shared_gate``/``shared_up`` (D, I * shared),
    ``shared_down``. ``layer`` tells the two by the leaves it is handed."""

    hidden_size: int = 7168
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    first_k_dense_replace: int = 1
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    expert_offset: int = 0
    experts_held: Optional[int] = None
    chunk_size: int = 512

    # ------------------------------------------------------------ shapes

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    def attention_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, H = self.hidden_size, self.num_attention_heads
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        nope, rope_dim, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        return {
            "attn_norm": (D,), "q_a": (D, rq), "q_a_norm": (rq,), "q_b": (rq, H * (nope + rope_dim)),
            "kv_a": (D, rkv + rope_dim), "kv_a_norm": (rkv,), "kv_b": (rkv, H * (nope + dv)),
            "wo": (H * dv, D), "mlp_norm": (D,),
        }

    def layer_shapes(self, layer: int = -1) -> Dict[str, Tuple[int, ...]]:
        """Layer ``layer``'s leaves (default: a routed layer's)."""
        D, I = self.hidden_size, self.moe_intermediate_size
        shapes = self.attention_shapes()
        if 0 <= layer < self.first_k_dense_replace:
            W = self.intermediate_size
            return {**shapes, "gate": (D, W), "up": (D, W), "down": (W, D)}
        S = I * self.n_shared_experts
        return {
            **shapes, "router": (D, self.n_routed_experts),
            "gate": (self.held, D, I), "up": (self.held, D, I), "down": (self.held, I, D),
            "shared_gate": (D, S), "shared_up": (D, S), "shared_down": (S, D),
        }

    def _rows_a_run(self, rows: int) -> int:
        """Rows of one call of the routed experts: the most whole chunks,
        up to ``_CHUNKS_A_RUN``, that divide ``rows``."""
        chunks = rows // self.chunk_size
        return self.chunk_size * max(
            c for c in range(1, min(chunks, _CHUNKS_A_RUN) + 1) if chunks % c == 0
        )

    def program_bytes(self, batch: int, rows: int) -> int:
        """Device bytes ``layer`` needs beside its arguments for ``batch``
        requests of ``rows`` padded rows, counted at its three widest
        points, whichever is largest: the attention's inputs (per row the
        normed state, queries, keys and values in float32 as the matmuls
        leave them and in bfloat16 as the kernel reads them), the dense
        layer's gate and up (float32) and their product (bfloat16), and one
        run of the routed experts (its pairs' sorted rows in bfloat16, gate
        and up, down's output and its gathered copy, float32); plus the
        residual stream three times over. What bounds a bank's batch. The
        TPU compiler's own analysis reads 1.17 GB for the dense layer and
        2.07 GB for a routed one at one request of 10 240 rows at the
        published sizes (``tests/test_tpu_compile.py``); this count reads
        3.10 GB. It doubles with the batch where the analysis grows by a
        fifth (2.52 GB at two requests: they attend one after another and
        a run of the experts is as long either way), which errs to the
        side of a smaller batch: on a v5e the benchmark's trunk and bank
        leave 5.82e9 bytes, so one request a call and not two."""
        D, H, I = self.hidden_size, self.num_attention_heads, self.moe_intermediate_size
        per_head = 2 * self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim
        attention = batch * rows * (6 * H * per_head + 2 * H * self.v_head_dim + 4 * D)
        dense = batch * rows * 10 * self.intermediate_size if self.first_k_dense_replace else 0
        run = self._rows_a_run(batch * rows) * self.num_experts_per_tok * (10 * D + 10 * I)
        return int(max(attention, dense, run) + batch * rows * 12 * D)

    def forward_flops_per_row(self, context_rows: int) -> float:
        """Forward FLOPs of one row of a ``context_rows``-row request,
        averaged over its positions: 2 a multiply-add of the matrices a row
        meets (the latent attention's five, the dense layers' three, a
        routed layer's router, shared expert and the held experts' share of
        its ``num_experts_per_tok`` at an even load) and the causal
        attention's scores and values."""
        D, H, I = self.hidden_size, self.num_attention_heads, self.moe_intermediate_size
        shapes = self.attention_shapes()
        attention = sum(math.prod(shapes[n]) for n in ("q_a", "q_b", "kv_a", "kv_b", "wo"))
        dense_layers = min(self.first_k_dense_replace, self.num_hidden_layers)
        routed_layers = self.num_hidden_layers - dense_layers
        routed = (D * self.n_routed_experts + 3 * D * I * self.n_shared_experts
                  + 3 * D * I * self.num_experts_per_tok * self.held / self.n_routed_experts)
        matrices = (self.num_hidden_layers * attention + dense_layers * 3 * D * self.intermediate_size
                    + routed_layers * routed + 2 * self.n_features * D)
        width = self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim
        attend = 2.0 * H * width * (int(context_rows) + 1) / 2.0
        return 2.0 * matrices + self.num_hidden_layers * attend

    def nominal_context_rows(self) -> int:
        """The request length a per-row FLOP count is quoted at: twenty
        chunks (10 240 rows at 512: a week of minutes, padded)."""
        return 20 * self.chunk_size

    # ----------------------------------------------------------- forward

    def _attention(self, w, x, interpret: bool):
        """``MLA(RMSNorm(x))``: (B, T, D) float32."""
        B, T, _ = x.shape
        H, rq, rkv = self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank
        nope, rope_dim, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        eps, positions = self.rms_norm_eps, jnp.arange(T)
        inv_freq, softmax_multiplier = yarn(rope_dim, self.rope_theta, self.rope_scaling)
        scale = softmax_multiplier / math.sqrt(nope + rope_dim)
        per_head = lambda x, m: jnp.einsum(  # (B, T, r) x (r, H * k) -> heads first (B, H, T, k)
            "btr,rhk->bhtk", x.astype(BF16), m.reshape(m.shape[0], H, -1), preferred_element_type=F32)
        turn = lambda x: rope(x[..., None, :], positions, self.rope_theta, inv_freq=inv_freq)[..., 0, :]
        with jax.named_scope("trunk/project"):
            h = _rmsnorm(x, w["attn_norm"], eps)
            q = per_head(_rmsnorm(_mm(h, w["q_a"]), w["q_a_norm"], eps), w["q_b"]) * scale
            latent = _mm(h, w["kv_a"])  # (B, T, rkv + rope)
            kv = per_head(_rmsnorm(latent[..., :rkv], w["kv_a_norm"], eps), w["kv_b"])
            args = (q[..., :nope], turn(q[..., nope:]), kv[..., :nope], turn(latent[..., rkv:]), kv[..., nope:])
            args = jax.tree.map(lambda a: a.astype(BF16), args)

        def attend(args):
            with jax.named_scope("trunk/attend"):
                return latent_attention(*args, granule=self.chunk_size, interpret=interpret)

        # one request at a time, as the other kind: requests share no keys
        out = jax.lax.map(attend, args)  # (B, H, T, dv) bfloat16
        with jax.named_scope("trunk/project"):
            return jnp.einsum("bhtv,hvd->btd", out, w["wo"].reshape(H, dv, -1), preferred_element_type=F32)

    def layer(self, w, x, n_valid, interpret: bool = False):
        """One decoder layer over the B x T rows of ``x`` (B, T, D), T a
        multiple of ``chunk_size``; ``n_valid`` (B,): rows beyond it are
        padding. A layer with a ``router`` among its leaves is routed, any
        other dense: a caller that jits this compiles it once for each
        kind, whatever the depth.

        Returns the next ``x`` and what the layer observed. A routed layer:
        ``experts`` (B, T, top_k) uint8, each row's experts of
        ``n_routed_experts``, and ``held_tokens`` (held,) int32, the valid
        rows routed to each held expert. A dense layer: nothing."""
        B, T, D = x.shape
        eps = self.rms_norm_eps
        x = x + self._attention(w, x, interpret)
        if "router" not in w:
            with jax.named_scope("trunk/dense_mlp"):
                h = _rmsnorm(x, w["mlp_norm"], eps)
                return x + _swiglu(h, w["gate"], w["up"], w["down"]), {}
        with jax.named_scope("trunk/route"):
            h = _rmsnorm(x, w["mlp_norm"], eps).reshape(B * T, D)
        with jax.named_scope("trunk/shared_expert"):
            shared = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        valid = (jnp.arange(T)[None, :] < n_valid[:, None]).reshape(-1)

        def run(args):
            rows, their_valid = args
            return expert_layer(
                rows, w, self.num_experts_per_tok, their_valid, interpret,
                expert_offset=self.expert_offset, scoring=self.scoring_func, n_group=self.n_group,
                topk_group=self.topk_group, scale=self.routed_scaling_factor,
            )

        rows = self._rows_a_run(B * T)
        if rows == B * T:
            y, experts, tokens = run((h, valid))
        else:
            y, experts, tokens = jax.lax.map(run, (h.reshape(-1, rows, D), valid.reshape(-1, rows)))
            tokens = jnp.sum(tokens, axis=0)
        return x + (y.reshape(B * T, D) + shared).reshape(B, T, D), {
            "experts": experts.reshape(B, T, -1).astype(jnp.uint8), "held_tokens": tokens,
        }


def _only_float32(compute_dtype: str) -> None:
    if compute_dtype != "float32":
        raise ValueError("the trunk fixes its own dtypes (bfloat16 operands, float32 accumulation)")


@register_model_builder(type="TrunkForecast")
def sparse_moe_decoder(n_features: int, compute_dtype: str = "float32", **sizes) -> SparseMoEDecoder:
    """Decoder trunk of sparse-attention + routed-expert layers; ``sizes``
    are the published config's keys (``SparseMoEDecoder``)."""
    _only_float32(compute_dtype)
    return SparseMoEDecoder(n_features=int(n_features), **sizes)


@register_model_builder(type="TrunkForecast")
def latent_moe_decoder(n_features: int, compute_dtype: str = "float32", **sizes) -> LatentMoEDecoder:
    """Decoder trunk of latent-attention layers, dense then routed beside a
    shared expert; ``sizes`` are the published config's keys
    (``LatentMoEDecoder``) and the held range of experts."""
    _only_float32(compute_dtype)
    return LatentMoEDecoder(n_features=int(n_features), **sizes)
