"""A decoder *trunk* shared by every machine of a bank, with per-machine
projections in front of it and behind it.

The trunk is a stack of pre-norm decoder layers. It has no tokens: a
machine's scaled sensor rows enter through that machine's own linear
projection ``tags -> hidden`` (as a multimodal decoder takes continuous
features in place of embedding rows) and leave through its own linear head
``hidden -> tags``. A request's rows are ONE causal sequence. Three kinds:

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
  chips by experts: ``ops/moe.py``). Where the configuration has
  ``indexer_types``, the attention runs under an indexer's selection of
  keys (``ops/sparse_attention.py``'s ``select_keys``) that a ``full``
  layer makes and the ``shared`` layers after it reuse.
- ``hybrid_moe_decoder`` (``HybridMoEDecoder``): every layer ONE mixer, as
  the published ``hybrid_override_pattern`` spells the layers out: a
  Mamba-2 state-space mixer scanned in chunks (``ops/ssd.py``), a routed
  layer of squared-ReLU experts beside a shared one (``ops/moe.py``, a held
  range as above), or grouped-query attention over every causal key
  (``ops/sparse_attention.py``'s kernel with no mask).

The walk through the layers carries the residual stream AND the selection
in force: ``layer(w, x, n_valid, selection)`` returns the next ``x``, the
selection it made or was handed (``None`` for a kind or a configuration
that shares none) and what it observed.

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
from gordo_components_tpu.ops.sparse_attention import (
    WITNESS_STRIDE, masked_attention, rope, select_and_attend, select_keys, witness_of,
)
from gordo_components_tpu.ops.ssd import ssd_scan

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
    ``chunk_size`` among them), ``layer_shapes`` and ``layer``
    (``(w, x, n_valid, selection, interpret) -> (x, selection, seen)``)."""

    n_features: int

    def member_shapes(self) -> Dict[str, Dict[str, Tuple[int, ...]]]:
        F, D = self.n_features, self.hidden_size
        return {"in_proj": {"kernel": (F, D), "bias": (D,)},
                "head": {"kernel": (D, F), "bias": (F,)}}

    def padded_rows(self, rows: int) -> int:
        """A request is never cut: it is padded to whole chunks."""
        return -(-int(rows) // self.chunk_size) * self.chunk_size

    def witness_stride(self) -> int:
        """Every how-manieth query's selection a layer that selects keys
        observes as its ``witness``."""
        return min(WITNESS_STRIDE, self.chunk_size)

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
                layer[name] = self._init_leaf(name, leaf_key, shape)
            layers.append(layer)
        return {"layers": layers, "final_norm": jnp.ones((self.hidden_size,), F32)}

    def _init_leaf(self, name: str, key, shape):
        if name.endswith(("_norm", "_scale")):
            return jnp.ones(shape, F32)
        if name.endswith("_bias"):
            return jnp.zeros(shape, F32)
        return self._uniform(key, shape, shape[-2], BF16)

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
        x, selection = self.embed(member["in_proj"], xs), None
        observed = []
        for w in trunk["layers"]:
            x, selection, seen = self.layer(w, x, n_valid, selection, interpret)
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

    def layer(self, w, x, n_valid, selection=None, interpret: bool = False):
        """One decoder layer over the B x T rows of ``x`` (B, T, D), T a
        multiple of ``chunk_size``; ``n_valid`` (B,): rows beyond it are
        padding. Every layer of this kind has the same shapes, so a caller
        that jits this compiles it once whatever the depth. Every layer
        makes its own selection (``select_and_attend``: ``select_keys``,
        then the grouped-query kernel) and hands none on: ``selection`` is
        taken and returned as it came (``None``).

        Returns the next ``x``, ``selection``, and what the layer observed:
        ``experts`` (B, T, top_k) uint8, ``witness`` (B, T // stride,
        T // 8) uint8 (``ops/sparse_attention.py``), ``expert_tokens``
        (E,), ``selections`` (B,) int32 and ``selection_uses`` () int32,
        1: this layer attended under a selection."""
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
        y, experts, tokens, _ = expert_layer(
            h, w, self.num_experts_per_tok, valid.reshape(-1), interpret
        )
        return x + y.reshape(B, T, D), selection, {
            "experts": experts.reshape(B, T, -1).astype(jnp.uint8), "witness": witness,
            "expert_tokens": tokens, "selections": selections,
            "selection_uses": jnp.ones((), jnp.int32),
        }


@dataclass(frozen=True)
class LatentMoEDecoder(_Trunk):
    """Multi-head latent attention in every layer; the first
    ``first_k_dense_replace`` layers then a dense SwiGLU, the rest a routed
    expert layer beside a shared expert. Key names are the published
    config's (the DeepSeek-V3 family's), but for the share:
    ``n_routed_experts`` is the router's width, and the trunk holds experts
    ``expert_offset .. expert_offset + experts_held`` of every routed layer
    (``experts_held=None``: all of them).

    The published keys present decide what a layer holds. With
    ``indexer_types`` (one entry a held layer, ``"full"`` or ``"shared"``,
    the first ``"full"``) the attention runs under a selection of
    ``index_topk`` keys a query: a ``full`` layer has an indexer
    (``index_n_heads`` heads of ``index_head_dim``, its queries from the
    NORMED QUERY LATENT, RoPE on the first ``qk_rope_head_dim`` of a head)
    and makes the selection, a ``shared`` layer has none and attends under
    the selection of the nearest ``full`` layer below it. Without the key no
    layer selects: every causal key. ``topk_method: "noaux_tc"`` gives every
    routed layer a ``router_bias`` that enters the choice of experts and not
    their weights (``ops/moe.py``).

    A layer's leaves (``layer_shapes``): ``attn_norm`` (D,), ``q_a``
    (D, q_lora_rank), ``q_a_norm``, ``q_b`` (q_lora_rank, H*(nope+rope)),
    ``kv_a`` (D, kv_lora_rank+rope), ``kv_a_norm``, ``kv_b`` (kv_lora_rank,
    H*(nope+v)), ``wo`` (H*v, D), ``mlp_norm`` (D,); a ``full`` layer's
    indexer: ``idx_wq`` (q_lora_rank, J*dI), ``idx_wk`` (D, dI),
    ``idx_k_scale``/``idx_k_bias`` (dI,), ``idx_ww`` (D, J); then, a dense
    layer: ``gate``/``up`` (D, intermediate_size), ``down``; a routed
    layer: ``router`` (D, n_routed_experts), ``router_bias``
    (n_routed_experts,) under ``noaux_tc``, ``gate``/``up`` (held, D, I),
    ``down`` (held, I, D), ``shared_gate``/``shared_up`` (D, I * shared),
    ``shared_down``. ``layer`` tells dense from routed and ``full`` from
    ``shared`` by the leaves it is handed."""

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
    topk_method: str = "greedy"
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Optional[Tuple[str, ...]] = None
    expert_offset: int = 0
    experts_held: Optional[int] = None
    chunk_size: int = 512

    def __post_init__(self):
        kinds = self.indexer_types
        if kinds is None:
            return
        if (len(kinds) != self.num_hidden_layers or kinds[0] != "full"
                or set(kinds) - {"full", "shared"}):
            raise ValueError(
                f"indexer_types {kinds!r}: one of 'full' or 'shared' for each of the "
                f"{self.num_hidden_layers} layers held, the first 'full' (a shared layer "
                "attends under the selection of a full layer below it)")

    # ------------------------------------------------------------ shapes

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    def _full_layers(self) -> int:
        """Layers that make a selection (0 without ``indexer_types``)."""
        return sum(kind == "full" for kind in self.indexer_types or ())

    def attention_shapes(self, full: bool = False) -> Dict[str, Tuple[int, ...]]:
        """The latent attention's leaves; ``full``: and an indexer's."""
        D, H = self.hidden_size, self.num_attention_heads
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        nope, rope_dim, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        shapes = {
            "attn_norm": (D,), "q_a": (D, rq), "q_a_norm": (rq,), "q_b": (rq, H * (nope + rope_dim)),
            "kv_a": (D, rkv + rope_dim), "kv_a_norm": (rkv,), "kv_b": (rkv, H * (nope + dv)),
            "wo": (H * dv, D), "mlp_norm": (D,),
        }
        if full:
            J, dI = self.index_n_heads, self.index_head_dim
            shapes.update({"idx_wq": (rq, J * dI), "idx_wk": (D, dI), "idx_k_scale": (dI,),
                           "idx_k_bias": (dI,), "idx_ww": (D, J)})
        return shapes

    def layer_shapes(self, layer: int = -1) -> Dict[str, Tuple[int, ...]]:
        """Layer ``layer``'s leaves (negative: from the last, the default
        the last layer's): dense below ``first_k_dense_replace`` and routed
        from there, with an indexer's where ``indexer_types`` calls the
        layer ``full``."""
        D, I = self.hidden_size, self.moe_intermediate_size
        layer = layer % self.num_hidden_layers
        shapes = self.attention_shapes(
            full=self.indexer_types is not None and self.indexer_types[layer] == "full")
        if layer < self.first_k_dense_replace:
            W = self.intermediate_size
            return {**shapes, "gate": (D, W), "up": (D, W), "down": (W, D)}
        S = I * self.n_shared_experts
        bias = {"router_bias": (self.n_routed_experts,)} if self.topk_method == "noaux_tc" else {}
        return {
            **shapes, "router": (D, self.n_routed_experts), **bias,
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
        1.01 GB for a routed one at one request of 10 240 rows at the
        published sizes (``tests/test_tpu_compile.py``; 2.07 GB while a
        run's passes were sized by all its pairs: since they go in blocks
        of held pairs, ``ops/moe.py``, the run's term here is an upper
        bound no load reaches); this count reads 3.10 GB. It doubles with
        the batch, and the analysis reads 2.52 GB at two requests (they
        attend one after another and a run of the experts is as long
        either way), so it errs to the side of a smaller batch: on a v5e
        the benchmark's trunk and bank leave 5.82e9 bytes, so one request
        a call and not two.

        Under ``indexer_types`` the attention's point also holds the
        selection: every request's (rows, rows) mask as the layer was
        handed it and as it hands it on (int8), one request's as
        ``select_keys`` builds it (bool), and one chunk's indexer dots
        (float32, every indexer head against every key). These 0.99 GB are
        ADDED to the attention's 3.36 GB as if they were live together, and
        they are not: the compiler's analysis of the three layer programs
        at one request of 10 240 rows reads 1.85 to 2.08 GB of temporaries
        and 0.36 GB of outputs (``tests/test_tpu_compile.py`` prints them)
        where this count reads 5.10 GB, so its whole need is under the
        attention's point alone and no two of the points coincide as
        counted. The selection at a point of its own (the kernel's
        bfloat16 inputs, the indexer's queries and the terms above: 2.08
        GB) would be the smaller of the two, and the count 4.11 GB a
        request and 8.22 GB for two where the benchmark's chip has 7.70e9
        free: one request a call either way, by 7% where the sum leaves
        22%. The sum was kept for that margin: a bank that took two would
        need a second warmed shape."""
        D, H, I = self.hidden_size, self.num_attention_heads, self.moe_intermediate_size
        per_head = 2 * self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim
        attention = batch * rows * (6 * H * per_head + 2 * H * self.v_head_dim + 4 * D)
        if self.indexer_types is not None:
            attention += ((2 * batch + 1) * rows * rows
                          + 4 * self.chunk_size * rows * self.index_n_heads)
        dense = batch * rows * 10 * self.intermediate_size if self.first_k_dense_replace else 0
        run = self._rows_a_run(batch * rows) * self.num_experts_per_tok * (10 * D + 10 * I)
        return int(max(attention, dense, run) + batch * rows * 12 * D)

    def selected_pairs(self, rows: int) -> int:
        """(query, key) pairs one layer attends over in a ``rows``-row
        request: the indexer's ``min(t + 1, index_topk)`` a query, or every
        causal pair where the configuration selects none."""
        n = int(rows)
        if self.indexer_types is None or n <= self.index_topk:
            return n * (n + 1) // 2
        k = self.index_topk
        return k * (k + 1) // 2 + (n - k) * k

    def forward_flops_per_row(self, context_rows: int) -> float:
        """Forward FLOPs of one row of a ``context_rows``-row request,
        averaged over its positions: 2 a multiply-add of the matrices a row
        meets (the latent attention's five in every layer, an indexer's
        three in the ``full`` ones, the ``first_k_dense_replace`` dense
        layers' three, a routed layer's router, shared expert and the held
        experts' share of its ``num_experts_per_tok`` at an even load), the
        attention's scores and values over the pairs it attends over (the
        selected ones under ``indexer_types``, else every causal pair), and
        a ``full`` layer's indexer scores over every causal pair."""
        D, H, I = self.hidden_size, self.num_attention_heads, self.moe_intermediate_size
        n = int(context_rows)
        shapes = self.attention_shapes(full=True)
        size = lambda *names: sum(math.prod(shapes[name]) for name in names)
        attention = size("q_a", "q_b", "kv_a", "kv_b", "wo")
        full_layers = self._full_layers()
        dense_layers = min(self.first_k_dense_replace, self.num_hidden_layers)
        routed_layers = self.num_hidden_layers - dense_layers
        routed = (D * self.n_routed_experts + 3 * D * I * self.n_shared_experts
                  + 3 * D * I * self.num_experts_per_tok * self.held / self.n_routed_experts)
        matrices = (self.num_hidden_layers * attention
                    + full_layers * size("idx_wq", "idx_wk", "idx_ww")
                    + dense_layers * 3 * D * self.intermediate_size
                    + routed_layers * routed + 2 * self.n_features * D)
        width = self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim
        attend = 2.0 * H * width * self.selected_pairs(n) / n
        index = 2.0 * self.index_n_heads * self.index_head_dim * (n + 1) / 2.0
        return 2.0 * matrices + self.num_hidden_layers * attend + full_layers * index

    def nominal_context_rows(self) -> int:
        """The request length a per-row FLOP count is quoted at: twenty
        chunks (10 240 rows at 512: a week of minutes, padded)."""
        return 20 * self.chunk_size

    # ----------------------------------------------------------- forward

    def _attention(self, w, x, n_valid, selection, interpret: bool):
        """``(MLA(RMSNorm(x)) (B, T, D) float32, the selection it ran
        under, what it observed)``. A layer with an indexer among its
        leaves makes the selection ((B, T, T) int8, the form the kernel
        reads tile by tile) and observes ``selections`` (``select_keys``);
        any other attends under the one it was handed, or over every causal
        key where it was handed none. EVERY layer that attends under a
        selection observes ``witness``, cut from the very array its kernel
        is handed: a layer that attended under another selection than it
        should have, a stale one or none of the indexer's, shows in the
        answer."""
        B, T, _ = x.shape
        H, rq, rkv = self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank
        nope, rope_dim, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        eps, positions = self.rms_norm_eps, jnp.arange(T)
        inv_freq, softmax_multiplier = yarn(rope_dim, self.rope_theta, self.rope_scaling)
        scale = softmax_multiplier / math.sqrt(nope + rope_dim)
        per_head = lambda x, m: jnp.einsum(  # (B, T, r) x (r, H * k) -> heads first (B, H, T, k)
            "btr,rhk->bhtk", x.astype(BF16), m.reshape(m.shape[0], H, -1), preferred_element_type=F32)
        turn = lambda x: rope(x, positions, self.rope_theta, rope_dim, inv_freq)  # (..., T, heads, d)
        one = lambda x: turn(x[..., None, :])[..., 0, :]
        seen = {}
        with jax.named_scope("trunk/project"):
            h = _rmsnorm(x, w["attn_norm"], eps)
            c_q = _rmsnorm(_mm(h, w["q_a"]), w["q_a_norm"], eps)
            q = per_head(c_q, w["q_b"]) * scale
            latent = _mm(h, w["kv_a"])  # (B, T, rkv + rope)
            kv = per_head(_rmsnorm(latent[..., :rkv], w["kv_a_norm"], eps), w["kv_b"])
            args = (q[..., :nope], one(q[..., nope:]), kv[..., :nope], one(latent[..., rkv:]), kv[..., nope:])
            args = jax.tree.map(lambda a: a.astype(BF16), args)
            if "idx_wq" in w:
                J, dI = self.index_n_heads, self.index_head_dim
                qi = turn(_mm(c_q, w["idx_wq"]).reshape(B, T, J, dI))
                ki = one(_layernorm(_mm(h, w["idx_wk"]), w["idx_k_scale"], w["idx_k_bias"]))
                wi = _mm(h, w["idx_ww"])
        if "idx_wq" in w:

            def select(indexer):
                return select_keys(*indexer, topk=self.index_topk, chunk=self.chunk_size,
                                   divisor=math.sqrt(J * dI))

            # one request at a time: a chunk's indexer dots are what bounds this
            mask, seen["selections"], _ = jax.lax.map(select, (qi, ki, wi, n_valid))
            with jax.named_scope("trunk/select"):
                selection = mask.astype(jnp.int8)
        if selection is not None:
            seen["selection_uses"] = jnp.ones((), jnp.int32)
            with jax.named_scope("trunk/select"):
                seen["witness"] = witness_of(selection, self.witness_stride())

        def attend(args):
            with jax.named_scope("trunk/attend"):
                return latent_attention(*args[0], granule=self.chunk_size, interpret=interpret,
                                        selection=args[1])

        # one request at a time, as the other kind: requests share no keys
        out = jax.lax.map(attend, (args, selection))  # (B, H, T, dv) bfloat16
        with jax.named_scope("trunk/project"):
            out = jnp.einsum("bhtv,hvd->btd", out, w["wo"].reshape(H, dv, -1), preferred_element_type=F32)
        return out, selection, seen

    def layer(self, w, x, n_valid, selection=None, interpret: bool = False):
        """One decoder layer over the B x T rows of ``x`` (B, T, D), T a
        multiple of ``chunk_size``; ``n_valid`` (B,): rows beyond it are
        padding; ``selection``: what the layer below handed on, (B, T, T)
        int8 or ``None``. A layer with a ``router`` among its leaves is
        routed, any other dense; one with an indexer (``idx_wq``) makes the
        selection it attends under and hands on, any other attends under
        the one it was handed and hands it on: a caller that jits this
        compiles it once for each kind, whatever the depth.

        Returns the next ``x``, the selection, and what the layer observed.
        A routed layer: ``experts`` (B, T, top_k) uint8, each row's experts
        of ``n_routed_experts``, ``held_tokens`` (held,) int32, the valid
        rows routed to each held expert, and ``held_blocks`` () int32, the
        blocks of held pairs its runs worked through (``ops/moe.py``). A
        layer that made a selection: ``selections`` as the other kind's;
        one that attended under a selection, its own or one handed on:
        ``selection_uses`` () int32, 1, and ``witness``, the sampled
        queries' keys in the selection its kernel read."""
        B, T, D = x.shape
        eps = self.rms_norm_eps
        attended, selection, seen = self._attention(w, x, n_valid, selection, interpret)
        x = x + attended
        if "router" not in w:
            with jax.named_scope("trunk/dense_mlp"):
                h = _rmsnorm(x, w["mlp_norm"], eps)
                return x + _swiglu(h, w["gate"], w["up"], w["down"]), selection, seen
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
            y, experts, tokens, blocks = run((h, valid))
        else:
            y, experts, tokens, blocks = jax.lax.map(
                run, (h.reshape(-1, rows, D), valid.reshape(-1, rows)))
            tokens, blocks = jnp.sum(tokens, axis=0), jnp.sum(blocks)
        return x + (y.reshape(B * T, D) + shared).reshape(B, T, D), selection, {
            **seen, "experts": experts.reshape(B, T, -1).astype(jnp.uint8), "held_tokens": tokens,
            "held_blocks": blocks,
        }


@dataclass(frozen=True)
class HybridMoEDecoder(_Trunk):
    """A hybrid decoder whose layers are each ONE mixer, ``x + mixer(
    RMSNorm(x))``: a Mamba-2 state-space mixer (``M``), a routed layer of
    squared-ReLU experts beside a shared one (``E``) or grouped-query
    attention over every causal key (``*``), as the published
    ``hybrid_override_pattern`` spells the layers out. Key names are the
    published config's (``nemotron_h``), but for the share:
    ``n_routed_experts`` is the router's width, and the trunk holds experts
    ``expert_offset .. expert_offset + experts_held`` of every routed layer
    (``experts_held=None``: all of them). ``held_layers``: which of the
    published layers the trunk holds (default the first
    ``num_hidden_layers``), and so which kind each held layer is.

    A layer's leaves (``layer_shapes``), ``input_norm`` (D,) in every kind:

    - ``M``: ``in_proj`` (D, 2 d_in + 2 G N + H), splitting into the gate
      ``z`` (d_in), ``xBC`` (d_in + 2 G N) and ``dt`` (H); ``conv`` (K, d_in
      + 2 G N) and ``conv_bias``, a causal depthwise convolution of ``xBC``
      followed by SiLU; ``dt_bias``, ``A_log`` and ``D`` (H,); the scan
      (``ops/ssd.py``); ``mixer_norm`` (d_in,), an RMSNorm over each of the
      G groups of ``y * silu(z)``; ``out_proj`` (d_in, D). d_in is
      ``mamba_num_heads * mamba_head_dim``, G ``n_groups``, N
      ``ssm_state_size``, K ``conv_kernel``.
    - ``E``: ``router`` (D, n_routed_experts), ``router_bias``
      (n_routed_experts,) (the published ``e_score_correction_bias``),
      ``up`` (held, D, I), ``down`` (held, I, D), ``shared_up`` (D, S),
      ``shared_down`` (S, D); every expert ``down(relu(up h)^2)``.
    - ``*``: ``wq`` (D, H d), ``wk``/``wv`` (D, G_kv d), ``wo`` (H d, D); no
      rotary embedding, scale d^-1/2.

    ``layer`` tells the kinds apart by the leaves it is handed (``A_log``:
    a mixer; ``router``: routed; any other: attention), so a caller that
    jits it compiles it once for each kind, whatever the depth."""

    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    held_layers: Optional[Tuple[int, ...]] = None
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    expert_offset: int = 0
    experts_held: Optional[int] = None
    chunk_size: int = 128

    def __post_init__(self):
        held = self.published_layers
        if (len(held) != self.num_hidden_layers
                or any(not 0 <= l < len(self.hybrid_override_pattern) for l in held)
                or set(self.hybrid_override_pattern) - set("ME*")):
            raise ValueError(
                f"held_layers {held!r}: one published layer for each of the "
                f"{self.num_hidden_layers} held, each a position of a pattern of 'M', 'E' and '*' "
                f"({self.hybrid_override_pattern!r})")

    # ------------------------------------------------------------ shapes

    @property
    def published_layers(self) -> Tuple[int, ...]:
        """The published layers held, in order."""
        if self.held_layers is None:
            return tuple(range(self.num_hidden_layers))
        return tuple(self.held_layers)

    @property
    def experts_here(self) -> int:
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    def kind(self, layer: int) -> str:
        """``M``, ``E`` or ``*``: held layer ``layer``'s published kind."""
        return self.hybrid_override_pattern[self.published_layers[layer]]

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def layer_shapes(self, layer: int = 0) -> Dict[str, Tuple[int, ...]]:
        """Held layer ``layer``'s leaves, by its kind."""
        D, kind = self.hidden_size, self.kind(layer)
        if kind == "M":
            H, d_in, conv = self.mamba_num_heads, self.d_inner, self.conv_width
            return {
                "input_norm": (D,), "in_proj": (D, d_in + conv + H),
                "conv": (self.conv_kernel, conv), "conv_bias": (conv,),
                "dt_bias": (H,), "A_log": (H,), "D": (H,), "mixer_norm": (d_in,),
                "out_proj": (d_in, D),
            }
        if kind == "E":
            I, S = self.moe_intermediate_size, self.moe_shared_expert_intermediate_size
            return {
                "input_norm": (D,), "router": (D, self.n_routed_experts),
                "router_bias": (self.n_routed_experts,),
                "up": (self.experts_here, D, I), "down": (self.experts_here, I, D),
                "shared_up": (D, S), "shared_down": (S, D),
            }
        H, G, d = self.num_attention_heads, self.num_key_value_heads, self.head_dim
        return {"input_norm": (D,), "wq": (D, H * d), "wk": (D, G * d), "wv": (D, G * d),
                "wo": (H * d, D)}

    def _init_leaf(self, name: str, key, shape):
        """The mixer's three vectors as the published initialisation draws
        them: ``A`` uniform on [1, 16), ``dt`` log-uniform between the time
        step's bounds (floored) and ``dt_bias`` its inverse softplus,
        ``D`` one."""
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
        if name == "dt_bias":
            lo, hi = math.log(self.time_step_min), math.log(self.time_step_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, F32, lo, hi)), self.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "D":
            return jnp.ones(shape, F32)
        return super()._init_leaf(name, key, shape)

    @property
    def attention_tile(self) -> int:
        """Rows of the attention kernel's score tile: two chunks (256 at the
        published 128; 512 rows with 32 query heads of 128 over-run a
        v5e's scoped VMEM)."""
        return 2 * self.chunk_size

    def padded_rows(self, rows: int) -> int:
        """A request is never cut: it is padded to whole attention tiles,
        each two whole chunks of the scan (10 080 rows -> 10 240)."""
        return -(-int(rows) // self.attention_tile) * self.attention_tile

    def program_bytes(self, batch: int, rows: int) -> int:
        """Device bytes ``layer`` needs beside its arguments for ``batch``
        requests of ``rows`` padded rows, counted at the mixer, the widest
        of the three kinds: per row its input projection, the convolution's
        padded input and output, the scan's output and the gated output
        (float32), the scan's head-major operands (bfloat16); plus the
        residual stream twice. What bounds a bank's batch. The TPU
        compiler's own analysis of the mixer reads 0.92 and 1.83 GB at 1 and
        2 requests of 10 240 rows at the published sizes, the routed layer
        0.83 and 1.02, attention 0.17 and 0.35 (``tests/test_tpu_compile.py``);
        this count reads 1.76 times the mixer's."""
        H, d_in, conv = self.mamba_num_heads, self.d_inner, self.conv_width
        mixer = 4 * (d_in + conv + H) + 10 * conv + 8 * d_in
        return int(batch * rows * (mixer + 8 * self.hidden_size))

    def scan_flops_per_row(self) -> float:
        """One mixer layer's scan, one row, in whole chunks: the chunk's
        ``C B^T`` a group, its decayed matrix times x, the carried state's
        term and the state's update a head."""
        Q, N, P = self.chunk_size, self.ssm_state_size, self.mamba_head_dim
        return 2.0 * Q * self.n_groups * N + 2.0 * Q * self.d_inner + 4.0 * self.d_inner * N

    def forward_flops_per_row(self, context_rows: int) -> float:
        """Forward FLOPs of one row of a ``context_rows``-row request,
        averaged over its positions: 2 a multiply-add of the matrices a row
        meets (a mixer's two projections, a routed layer's router, shared
        expert and the held experts' share of its ``num_experts_per_tok`` at
        an even load, attention's four), each mixer's scan in whole chunks,
        and attention's scores and values over every causal key."""
        n, D = int(context_rows), self.hidden_size
        kinds = [self.kind(l) for l in range(self.num_hidden_layers)]
        shapes = {kind: self.layer_shapes(kinds.index(kind)) for kind in set(kinds)}
        size = lambda kind, *names: sum(math.prod(shapes[kind][name]) for name in names)
        per_kind = {
            "M": 2.0 * size("M", "in_proj", "out_proj") + self.scan_flops_per_row(),
            "E": 2.0 * (size("E", "router", "shared_up", "shared_down")
                        + 2 * D * self.moe_intermediate_size * self.num_experts_per_tok
                        * self.experts_here / self.n_routed_experts),
            "*": 2.0 * size("*", "wq", "wk", "wv", "wo")
                 + 4.0 * self.num_attention_heads * self.head_dim * (n + 1) / 2.0,
        }
        return sum(per_kind[kind] for kind in kinds) + 2.0 * 2 * self.n_features * D

    def nominal_context_rows(self) -> int:
        """The request length a per-row FLOP count is quoted at: eighty
        chunks (10 240 rows at 128: a week of minutes, padded)."""
        return 80 * self.chunk_size

    # ----------------------------------------------------------- forward

    def _mixer(self, w, x, n_valid, interpret: bool):
        B, T, _ = x.shape
        H, P, G, N, K = (self.mamba_num_heads, self.mamba_head_dim, self.n_groups,
                         self.ssm_state_size, self.conv_kernel)
        d_in, eps = self.d_inner, self.rms_norm_eps
        with jax.named_scope("trunk/mamba/in_proj"):
            proj = _mm(_rmsnorm(x, w["input_norm"], eps), w["in_proj"])
            z, xBC, dt = proj[..., :d_in], proj[..., d_in:d_in + self.conv_width], proj[..., -H:]
        with jax.named_scope("trunk/mamba/conv"):
            padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))  # causal: row t sees t - K + 1 .. t
            kernel = w["conv"].astype(F32)
            xBC = jax.nn.silu(sum(padded[:, k:k + T] * kernel[k] for k in range(K)) + w["conv_bias"])
            xs = xBC[..., :d_in].reshape(B, T, H, P)
            Bm = xBC[..., d_in:d_in + G * N].reshape(B, T, G, N)
            Cm = xBC[..., d_in + G * N:].reshape(B, T, G, N)
        with jax.named_scope("trunk/mamba/scan"):
            delta = jax.nn.softplus(dt + w["dt_bias"])
            y = ssd_scan(xs, delta, -jnp.exp(w["A_log"]), Bm, Cm, w["D"], self.chunk_size, interpret)
        with jax.named_scope("trunk/mamba/norm"):
            y = (y.reshape(B, T, d_in) * jax.nn.silu(z)).reshape(B, T, G, d_in // G)
            y = _rmsnorm(y, 1.0, eps).reshape(B, T, d_in) * w["mixer_norm"]
        with jax.named_scope("trunk/mamba/out_proj"):
            out = x + _mm(y, w["out_proj"])
        chunks = (n_valid + self.chunk_size - 1) // self.chunk_size
        return out, {"ssm_chunks": chunks.astype(jnp.int32)}

    def _routed(self, w, x, n_valid, interpret: bool):
        B, T, D = x.shape
        with jax.named_scope("trunk/route"):
            h = _rmsnorm(x, w["input_norm"], self.rms_norm_eps).reshape(B * T, D)
        with jax.named_scope("trunk/shared_expert"):
            shared = _mm(jnp.square(jax.nn.relu(_mm(h, w["shared_up"]))), w["shared_down"])
        valid = (jnp.arange(T)[None, :] < n_valid[:, None]).reshape(-1)
        y, experts, tokens, blocks = expert_layer(
            h, w, self.num_experts_per_tok, valid, interpret, expert_offset=self.expert_offset,
            scoring="sigmoid", n_group=self.n_group, topk_group=self.topk_group,
            scale=self.routed_scaling_factor,
        )
        return x + (y + shared).reshape(B, T, D), {
            "experts": experts.reshape(B, T, -1).astype(jnp.uint8), "held_tokens": tokens,
            "held_blocks": blocks,
        }

    def _attention(self, w, x, n_valid, interpret: bool):
        B, T, _ = x.shape
        H, G, d = self.num_attention_heads, self.num_key_value_heads, self.head_dim
        heads_first = lambda a, n: a.reshape(B, T, n, d).transpose(0, 2, 1, 3).astype(BF16)
        with jax.named_scope("trunk/project"):
            h = _rmsnorm(x, w["input_norm"], self.rms_norm_eps)
            q = heads_first(_mm(h, w["wq"]), H).reshape(B, G, H // G, T, d)
            k, v = heads_first(_mm(h, w["wk"]), G), heads_first(_mm(h, w["wv"]), G)
        def attend(args):
            with jax.named_scope("trunk/attention"):
                return masked_attention(*args, None, self.attention_tile, interpret)

        # one request at a time, as the other kinds: requests share no keys
        out = jax.lax.map(attend, (q, k, v))  # (B, G, H / G, T, d)
        with jax.named_scope("trunk/project"):
            out = out.reshape(B, H, T, d).transpose(0, 2, 1, 3).reshape(B, T, H * d)
            return x + _mm(out, w["wo"]), {}

    def layer(self, w, x, n_valid, selection=None, interpret: bool = False):
        """One layer over the B x T rows of ``x`` (B, T, D), T a multiple
        of ``attention_tile``; ``n_valid`` (B,): rows beyond it are padding,
        and every mixer is causal, so they reach no valid row. No kind
        selects keys: ``selection`` is taken and returned as it came.

        Returns the next ``x``, ``selection``, and what the layer observed:
        a mixer ``ssm_chunks`` (B,) int32, the chunks of valid rows it
        scanned a request; a routed layer ``experts`` (B, T, top_k) uint8,
        ``held_tokens`` (held,) int32 and ``held_blocks`` () int32, as
        ``LatentMoEDecoder``'s; attention nothing."""
        if "A_log" in w:
            x, seen = self._mixer(w, x, n_valid, interpret)
        elif "router" in w:
            x, seen = self._routed(w, x, n_valid, interpret)
        else:
            x, seen = self._attention(w, x, n_valid, interpret)
        return x, selection, seen


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
    if sizes.get("indexer_types") is not None:  # a JSON list
        sizes["indexer_types"] = tuple(sizes["indexer_types"])
    return LatentMoEDecoder(n_features=int(n_features), **sizes)


@register_model_builder(type="TrunkForecast")
def hybrid_moe_decoder(n_features: int, compute_dtype: str = "float32", **sizes) -> HybridMoEDecoder:
    """Decoder trunk of one-mixer layers (Mamba-2, routed squared-ReLU
    experts beside a shared one, causal attention) as the published pattern
    spells them; ``sizes`` are the published config's keys
    (``HybridMoEDecoder``), the held layers and the held range of experts."""
    _only_float32(compute_dtype)
    if sizes.get("held_layers") is not None:  # a JSON list
        sizes["held_layers"] = tuple(sizes["held_layers"])
    return HybridMoEDecoder(n_features=int(n_features), **sizes)
