"""wireless_psum — the paper's biased aggregation as a mesh collective.

TPU adaptation (DESIGN.md §2): the OTA MAC superposition *is* an
all-reduce; the biased OTA-FL update (6) becomes

    ghat = ( psum_m( chi_m * gamma_m * g_m )  +  z ) / alpha

executed inside ``shard_map`` with the FL clients laid out along the
("pod","data") mesh axes and the model axis left automatic.  Digital FL
quantizes each client's payload (dithered stochastic uniform quantizer —
the Pallas kernel in kernels/dithered_quant.py) before the reduce:

    ghat = psum_m( chi_m * dequant(quant(g_m, r_m)) / nu_m )

Per-round randomness (fading indicators chi, client weights) is computed
*outside* jit from the channel model and fed in as small arrays, so the
lowered step is shape-stable across rounds.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class WirelessRound:
    """Per-round, per-client aggregation inputs (leading dim = clients,
    reshaped to the client mesh axes by the caller)."""

    weight: jnp.ndarray        # chi_m*gamma_m (OTA) or chi_m/nu_m (digital)
    alpha: jnp.ndarray         # scalar post-scaler (OTA; 1.0 for digital)
    noise_scale: jnp.ndarray   # scalar: sqrt(N0)/alpha (OTA; 0 for digital)
    levels: jnp.ndarray        # quantizer levels 2^r - 1 per client (digital)


def wireless_psum(grads, round_info: WirelessRound, client_axes: tuple,
                  key: jax.Array, *, mode: str = "ota",
                  use_kernel: bool = True, skip_psum=None):
    """Biased wireless aggregation of per-client gradient pytrees.

    Must be called inside shard_map with ``client_axes`` manual.
    ``round_info.weight`` etc. are the *local* (already sliced) scalars.

    ``skip_psum``: optional bool pytree (same structure as grads) marking
    leaves that are *manual-sharded over a client axis* (expert-parallel
    weights): their gradients are already globally aggregated by the
    backward all_to_all, so the reduce is skipped and only the epilogue
    (post-scale / noise / quantize) applies.
    """
    # Aggregation happens in f32 regardless of the model dtype: (a) the
    # paper's update is real-valued analog superposition, and low-precision
    # reduction would add an unmodeled quantization term to Lemma 1; (b) the
    # XLA CPU backend miscompiles bf16 all-reduce under partial-auto
    # shard_map ("Invalid binary instruction opcode copy"), so the f32 cast
    # also keeps the dry-run healthy. Cast back to the leaf dtype after.
    w = round_info.weight.reshape(()).astype(jnp.float32)
    dtypes = jax.tree.map(lambda g: g.dtype, grads)
    if skip_psum is None:
        skip_psum = jax.tree.map(lambda _: False, grads)

    def cast_back(tree):
        return jax.tree.map(lambda g, dt: g.astype(dt), tree, dtypes)

    def reduce_leaf(g, skip):
        g = g.astype(jnp.float32)
        return g if skip else jax.lax.psum(g, client_axes)

    if mode == "ideal":
        n = 1
        for a in client_axes:
            n *= jax.lax.axis_size(a)
        return cast_back(jax.tree.map(
            lambda g, s: reduce_leaf(g, s) / n, grads, skip_psum))
    if mode == "ota":
        summed = jax.tree.map(
            lambda g, s: reduce_leaf(g * w.astype(g.dtype), s),
            grads, skip_psum)
        leaves = jax.tree.leaves(summed)
        keys = jax.random.split(key, len(leaves))
        keys = jax.tree.unflatten(jax.tree.structure(summed), keys)

        def epilogue(g, k):
            # fused post-scale + AWGN injection (Pallas kernel on TPU)
            return kops.ota_combine(g, round_info.alpha,
                                    round_info.noise_scale, k,
                                    use_kernel=use_kernel)
        return cast_back(jax.tree.map(epilogue, summed, keys))
    if mode == "digital":
        levels = round_info.levels.reshape(())
        # fold the client index into the dither key so clients draw
        # independent dither even though the key operand is replicated
        cidx = jnp.zeros((), jnp.int32)
        for a in client_axes:
            cidx = cidx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        key = jax.random.fold_in(key, cidx)
        leaves = jax.tree.leaves(grads)
        keys = jax.random.split(key, len(leaves))
        keys = jax.tree.unflatten(jax.tree.structure(grads), keys)

        def quantize(g, k):
            gq = kops.dithered_quantize(g.astype(jnp.float32), levels, k,
                                        use_kernel=use_kernel)
            return gq * w
        quantized = jax.tree.map(quantize, grads, keys)
        return cast_back(jax.tree.map(reduce_leaf, quantized, skip_psum))
    raise ValueError(mode)
