"""One Pallas program a training step for a gang of dense autoencoders.

``vmap(value_and_grad) + optax.adam`` trains a gang as separate batched
ops (forward, two backward products, one update fusion a leaf), and each
streams EVERY member's leaves through HBM: a member's parameters, as much
again for each Adam moment and for the gradient, ten or more times a step.
A member's whole training state is a few MB, so here the step is one
kernel with a grid over members: grid step ``i`` brings member ``i``'s
kernels, biases and both moments of each into VMEM with its batch, runs
forward, masked MSE, backward and Adam's update there, and writes
parameters and moments back in place (``input_output_aliases``). A state
leaf crosses HBM once each way a step; gradients and activations never do.

What the step computes is ``models/train_core.make_train_fns``'s, member
for member: ``ops/losses.mse_loss`` over the batch's real rows, Adam as
``optax.adam`` under ``inject_hyperparams`` (every hyperparameter read
from the member's own state, bias correction from the count after the
increment), and nothing at all (count included) for a member whose batch
is padding alone or that early stopping has frozen. Such a member is not
even fetched: its blocks map to its predecessor's (``src``), which the
pipeline already holds.

Arithmetic: parameters, moments, gradient accumulation and the update are
float32. Matmul operands are rounded as the platform's default precision
rounds them for the vmapped path (TPU: bfloat16 operands, float32
accumulation; ``interpret`` mode on the CPU: float32). The update is
optax's expression with the two bias corrections folded into per-member
scalars, ``p - (lr*sqrt(c2)/c1) * m / (sqrt(v + eps_root*c2) + eps*sqrt(c2))``:
one division an element instead of three, equal to optax's up to float32
rounding of the update (tests/test_dense_step.py pins the band).

Which buckets take it is :func:`resolve`'s to say, a pure function of
module, loss, optimizer, shapes and platform; ``parallel/fleet.py``
resolves it once per program key.
"""

import functools
from types import MappingProxyType
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from gordo_components_tpu.models.factories.feedforward import (
    FeedForwardAutoEncoder,
    resolve_activation,
)

LANE, SUBLANE = 128, 8
# platform -> how the kernel runs there. A CPU backend is not listed: its
# gangs keep vmap(epoch). (Tests hand :func:`resolve` ``{"cpu": "interpret"}``.)
MODES: Mapping[str, str] = MappingProxyType({"tpu": "pallas"})
# what one grid step may hold of the chip's VMEM (v5e: 128 MiB): a member's
# tiled state four times over (blocks in and out, each double-buffered),
# its batch, and the step's intermediates
VMEM_BUDGET = 96 << 20
# a member's real state (parameters and both moments, float32) from which
# the kernel is the faster epoch program. Every leaf crosses as whole 8 x 128
# tiles and a grid step costs ~5 us whatever it moves, so a narrow member
# pays for bytes it does not have: on the chip (tools/dense_step_ladder.py,
# default hourglasses, 16 steps of 100 rows; PERF.md section 6, PR 30) a
# 10-tag gang of 4096 (5 kB a member, 168 kB as tiled) took 1.66 times
# vmap(epoch)'s time, 20 and 30 tags 1.2 times, 45 tags (91 kB) the same,
# 60 tags (163 kB) 0.93 at 2048 members, 80 tags (289 kB) 0.80, 100 to 300
# tags 0.44 to 0.54; and fewer members favour vmap(epoch) (100 tags: 0.54
# at 2048 members, 0.75 at 512). The line is drawn between the last rung
# that gained little and the first that gained clearly.
NARROWEST_STATE_BYTES = 256 << 10
_N_SCALARS = 8  # per-member float32 scalars the step hands the kernel

# the chip's kernel compiler has no ``expm1``: below zero ``elu`` is
# ``exp(z) - 1`` here, within 6e-8 of the module's own
_FORWARD = {"elu": lambda z: jnp.where(z > 0, z, jnp.exp(jnp.minimum(z, 0.0)) - 1.0)}
# f'(z) from the pre-activation z and the activation's own output h, as
# jax differentiates models/factories/feedforward._ACTIVATIONS; None: 1
_DERIVATIVES = {
    "tanh": lambda z, h: 1.0 - h * h,
    "relu": lambda z, h: (z > 0).astype(z.dtype),
    "sigmoid": lambda z, h: h * (1.0 - h),
    "elu": lambda z, h: jnp.where(z > 0, 1.0, h + 1.0),
    "softplus": lambda z, h: jnp.exp(z - h),
    "linear": None,
}


def chain_of(module: FeedForwardAutoEncoder) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """``(widths, activations)`` of the module's ``Dense`` chain, input to
    output: ``Dense_l`` maps ``widths[l]`` to ``widths[l + 1]`` under
    ``activations[l]``."""
    widths = (module.n_features, *module.encoding_dim, *module.decoding_dim, module.n_features)
    funcs = (*module.encoding_func, *module.decoding_func, module.out_func)
    return tuple(int(w) for w in widths), tuple(funcs)


def _tiled_bytes(rows: int, cols: int) -> int:
    """Bytes of a float32 (rows, cols) block as VMEM and HBM tile it."""
    return 4 * (-(-rows // SUBLANE) * SUBLANE) * (-(-cols // LANE) * LANE)


def transposed(widths: Tuple[int, ...]) -> Tuple[bool, ...]:
    """A layer at a time: whether its kernel (and its moments) cross as the
    transpose, (out, in). Tiles are 8 x 128, so (250, 300) moves as 256 x 384
    and its transpose as 304 x 256, a fifth fewer bytes; the matmuls contract
    whichever dimension that leaves them."""
    return tuple(_tiled_bytes(o, i) < _tiled_bytes(i, o) for i, o in zip(widths, widths[1:]))


def state_bytes(widths: Tuple[int, ...]) -> Tuple[int, int]:
    """``(real, tiled)`` bytes of one member's parameters and both moments:
    as float32 numbers, and as the 8 x 128 tiles the kernel's blocks move."""
    shapes = list(zip(widths, widths[1:]))
    real = 3 * 4 * sum(i * o + o for i, o in shapes)
    tiled = 3 * sum(
        min(_tiled_bytes(i, o), _tiled_bytes(o, i)) + _tiled_bytes(1, o) for i, o in shapes
    )
    return real, tiled


def vmem_bytes(widths: Tuple[int, ...], batch_size: int) -> int:
    """What one grid step asks of VMEM: parameters and both moments as
    tiled, in and out, double-buffered; the batch double-buffered; and room
    for the step's own values (activations and their derivatives a layer,
    the widest layer's gradient, operands rounded for the matmuls)."""
    state = state_bytes(widths)[1]
    act = _tiled_bytes(batch_size, max(widths))
    widest = max(min(_tiled_bytes(i, o), _tiled_bytes(o, i)) for i, o in zip(widths, widths[1:]))
    return 4 * state + 2 * act + 3 * len(widths) * act + state // 3 + 4 * widest + (2 << 20)


def resolve(
    module, loss: str, opt_name: str, seq, batch_size: int, platform: str,
    modes: Mapping[str, str] = MODES, narrowest: int = NARROWEST_STATE_BYTES,
) -> Tuple[Optional[str], Optional[str]]:
    """``(mode, None)`` where the bucket takes the kernel, run as ``mode``
    (``modes[platform]``); else ``(None, the condition that failed)``, in
    words an operator reads in the ``fit:<bucket>`` span. ``narrowest``: a
    member's real state in bytes below which ``vmap(epoch)`` is the faster
    program (:data:`NARROWEST_STATE_BYTES`)."""

    def refusal() -> Optional[str]:
        if seq is not None or type(module) is not FeedForwardAutoEncoder:
            return f"module {type(module).__name__} is not a FeedForwardAutoEncoder"
        if module.compute_dtype != "float32":
            return f"compute_dtype {module.compute_dtype}"
        if loss != "mse":
            return f"loss {loss}"
        if opt_name.lower() != "adam":
            return f"optimizer {opt_name}"
        widths, funcs = chain_of(module)
        unknown = sorted(set(funcs) - set(_DERIVATIVES))
        if unknown:
            return f"activation {unknown[0]}"
        if platform not in modes:
            return f"platform {platform}"
        real, tiled = state_bytes(widths)
        if real < narrowest:
            return (
                f"member too narrow: {real >> 10} KiB of state ({tiled >> 10} as tiled), "
                f"the kernel pays from {narrowest >> 10}"
            )
        need = vmem_bytes(widths, batch_size)
        if need > VMEM_BUDGET:
            return f"member needs {need >> 20} MiB of VMEM, budget {VMEM_BUDGET >> 20}"
        return None

    refused = refusal()
    return (modes[platform], None) if refused is None else (None, refused)


def _kernel(funcs, flips, operand_dtype, src_ref, rows_ref, t_ref, x_ref, scalars_ref, *refs):
    """Grid step ``i``: member ``i``'s step, if it has one. ``refs``: the
    member's 6L state leaves (a layer's kernel, then its bias; of each the
    parameter, the first and the second moment); the same again as outputs;
    and its loss. ``scalars_ref``: (1, 8), as ``_step`` unpacks."""
    from jax.experimental import pallas as pl

    del src_ref, t_ref  # consumed by the index maps
    n = 6 * len(funcs)
    state_in, state_out, loss_ref = refs[:n], refs[n:2 * n], refs[2 * n]
    i = pl.program_id(0)
    n_real = rows_ref[i]

    @pl.when(n_real > 0)
    def _step():
        one_b1, b1, one_b2, b2, step, eps, eps_root, inv_denom = (
            scalars_ref[:, k:k + 1] for k in range(_N_SCALARS)
        )

        def adam(j, g):
            """Leaf ``j`` (a layer's kernel, then its bias) under gradient ``g``."""
            p_in, m_in, v_in = state_in[3 * j:3 * j + 3]
            p_out, m_out, v_out = state_out[3 * j:3 * j + 3]
            m = one_b1 * g + b1 * m_in[...]
            v = one_b2 * (g * g) + b2 * v_in[...]
            p_out[...] = p_in[...] - step * (m / (jnp.sqrt(v + eps_root) + eps))
            m_out[...] = m
            v_out[...] = v

        def rounded(a):
            return a.astype(operand_dtype)

        def matmul(a, b, over_a, over_b):
            return jax.lax.dot_general(
                a, b, (((over_a,), (over_b,)), ((), ())), preferred_element_type=jnp.float32
            )

        x = x_ref[...]
        h, hs, ws, acts = x, [], [], []
        for l, func in enumerate(funcs):
            hs.append(rounded(h))
            ws.append(rounded(state_in[6 * l][...]))
            z = matmul(hs[l], ws[l], 1, 1 if flips[l] else 0) + state_in[6 * l + 3][...]
            h = (_FORWARD.get(func) or resolve_activation(func))(z)
            acts.append((z, h))
        # ops/losses.mse_loss over the real rows, which the shuffle packs first
        real = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < n_real
        diff = jnp.where(real, h - x, 0.0)
        total = jnp.sum(jnp.sum(diff * diff, axis=1, keepdims=True), axis=0, keepdims=True)
        loss_ref[...] = jnp.broadcast_to(total * inv_denom, loss_ref.shape)
        delta = diff * (2.0 * inv_denom)
        for l in reversed(range(len(funcs))):
            slope = _DERIVATIVES[funcs[l]]
            if slope is not None:
                delta = delta * slope(*acts[l])
            d = rounded(delta)
            if l:
                # before the update below: the input gradient is the OLD kernel's
                below = matmul(d, ws[l], 1, 0 if flips[l] else 1)
            # the kernel's gradient, in the orientation the kernel crosses in
            adam(2 * l, matmul(d, hs[l], 0, 0) if flips[l] else matmul(hs[l], d, 0, 0))
            adam(2 * l + 1, jnp.sum(delta, axis=0, keepdims=True))
            if l:
                delta = below

    @pl.when(n_real <= 0)
    def _idle():
        loss_ref[...] = jnp.zeros(loss_ref.shape, loss_ref.dtype)

        # an idle member's blocks are its predecessor's (``src``), already
        # written; only a gang that OPENS with idle members holds member 0's
        # own state here, which goes back as it came
        @pl.when(i == 0)
        def _():
            for a, b in zip(state_in, state_out):
                b[...] = a[...]


def _call(funcs, widths, interpret, operand_dtype, src, rows, t, scalars, Xs, leaves):
    """The kernel over the gang: ``leaves`` is 6L arrays, a layer's kernel
    (M, in, out), or (M, out, in) where :func:`transposed` says so, and then
    its bias (M, 1, out); of each the parameter, the first and the second
    moment. They are separate operands because the chip keeps that many
    copies in flight at once (stacked three to an operand a step read 0.3 ms
    slower: PERF.md section 6, PR 30). ``scalars``: (M, 1, 8) as the
    kernel's ``_step`` unpacks them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, _, batch_size, F = Xs.shape
    member_block = lambda a: pl.BlockSpec(
        (None,) + a.shape[1:], lambda i, src, rows, t: (src[i],) + (0,) * (a.ndim - 1),
        memory_space=pltpu.VMEM,
    )
    state_specs = [member_block(a) for a in leaves]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(M,),
        in_specs=[
            pl.BlockSpec(
                (None, None, batch_size, F),
                lambda i, src, rows, t: (src[i], t[0], 0, 0),
                memory_space=pltpu.VMEM,
            ),
            member_block(scalars),
            *state_specs,
        ],
        out_specs=[
            *state_specs,
            pl.BlockSpec(
                (None, 1, LANE), lambda i, src, rows, t: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, funcs, transposed(widths), operand_dtype),
        grid_spec=grid_spec,
        out_shape=[
            *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves),
            jax.ShapeDtypeStruct((M, 1, LANE), jnp.float32),
        ],
        # state leaf j: operand 5 + j (after the three prefetched arrays,
        # the batch and the members' scalars) is output j, updated in place
        input_output_aliases={5 + j: j for j in range(len(leaves))},
        compiler_params=pltpu.CompilerParams(
            # a run of idle members revisits its predecessor's output block
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(widths, batch_size),
        ),
        interpret=interpret,
        name="dense_train_step",
    )(src, rows, t, Xs, scalars, *leaves)
    return out[:-1], out[-1][:, 0, 0]


def make_step(module: FeedForwardAutoEncoder, mode: str):
    """``(enter, step, leave)`` over a gang's STACKED state (the pytrees
    ``vmap(init_fn)`` builds: flax ``Dense_l`` kernels and biases,
    ``inject_hyperparams(adam)``'s state). ``mode``: ``pallas`` or
    ``interpret``.

    - ``enter(params, opt_state) -> carry``: the leaves in the kernel's
      order and orientation (biases as (M, 1, out), kernels transposed
      where that moves fewer bytes) and the two step counts; what an
      epoch's scan carries, so that nothing is laid out again inside it;
    - ``step(carry, hyperparams, Xs, t, n_real, active) -> (carry,
      losses)``: ``Xs`` (M, batches, batch, F) shuffled rows, real rows
      packed first; ``t`` which batch; ``n_real`` (M,) real rows in it (a
      0/1 row mask's sum); ``active`` (M,) > 0 for members still training;
      ``hyperparams`` the opt state's own, (M,) each;
    - ``leave(carry, opt_state) -> (params, opt_state)``: back into the
      pytrees entered (``opt_state``: the one entered, for the rest of it).
    """
    widths, funcs = chain_of(module)
    names = [f"Dense_{l}" for l in range(len(funcs))]
    flips = transposed(widths)
    # matmul operands as the platform's default precision rounds the
    # vmapped path's: bfloat16 on the chip, float32 where XLA's CPU runs it
    interpret, operand_dtype = {
        "pallas": (False, jnp.bfloat16), "interpret": (True, jnp.float32),
    }[mode]

    def enter(params, opt_state):
        adam_state = opt_state.inner_state[0]
        trees = [t["params"] for t in (params, adam_state.mu, adam_state.nu)]
        leaves = []
        for name, flip in zip(names, flips):
            leaves += [
                jnp.swapaxes(t[name]["kernel"], 1, 2) if flip else t[name]["kernel"]
                for t in trees
            ]
            # (M, 1, out): a (1, out) block of an (M, out) array is a
            # lowering the chip's compiler refuses (tests/test_tpu_compile.py)
            leaves += [t[name]["bias"][:, None, :] for t in trees]
        return leaves, adam_state.count, opt_state.count

    def step(carry, hp, Xs, t, n_real, active):
        leaves, adam_count, count = carry
        M = n_real.shape[0]
        rows = jnp.where(active > 0, n_real, 0).astype(jnp.int32)
        steps = rows > 0
        # an idle member takes its predecessor's blocks: nothing is fetched
        # for it and nothing of it is written
        src = jax.lax.cummax(jnp.where(steps, jnp.arange(M, dtype=jnp.int32), 0))
        stepped = optax.safe_increment(adam_count)
        c1 = 1 - hp["b1"] ** stepped
        c2 = 1 - hp["b2"] ** stepped
        root = jnp.sqrt(c2)
        scalars = jnp.stack(
            [
                1 - hp["b1"], hp["b1"], 1 - hp["b2"], hp["b2"],
                hp["learning_rate"] * root / c1, hp["eps"] * root, hp["eps_root"] * c2,
                1.0 / (jnp.maximum(n_real, 1.0) * widths[0]),
            ],
            axis=1,
        ).astype(jnp.float32)
        leaves, losses = _call(
            funcs, widths, interpret, operand_dtype, src, rows,
            jnp.reshape(t, (1,)).astype(jnp.int32), scalars[:, None, :], Xs, leaves,
        )
        carry = (
            list(leaves),
            jnp.where(steps, stepped, adam_count),
            jnp.where(steps, optax.safe_increment(count), count),
        )
        return carry, losses

    def leave(carry, opt_state):
        leaves, adam_count, count = carry
        kernel = lambda l, g: (
            jnp.swapaxes(leaves[6 * l + g], 1, 2) if flips[l] else leaves[6 * l + g]
        )
        params, mu, nu = (
            {"params": {
                name: {"kernel": kernel(l, g), "bias": leaves[6 * l + 3 + g][:, 0]}
                for l, name in enumerate(names)
            }}
            for g in range(3)
        )
        adam_state = opt_state.inner_state[0]._replace(count=adam_count, mu=mu, nu=nu)
        return params, opt_state._replace(
            count=count, inner_state=(adam_state,) + tuple(opt_state.inner_state[1:])
        )

    return enter, step, leave
