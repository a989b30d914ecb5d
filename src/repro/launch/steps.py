"""Step factories: FL train step (wireless collective over client axes),
serve prefill step, and single-token decode step — each returned as a
``StepBundle`` (fn + shardings + abstract inputs) consumed by the dry-run,
benchmarks and the real drivers alike.

Client layout: FL clients are the ("pod","data") mesh slices. The train
step runs under ``jax.shard_map`` with those axes manual and the "model"
axis automatic, so tensor-parallel math inside the model is partitioned by
XLA SPMD while the gradient aggregation is the explicit wireless collective
(core/collectives.wireless_psum).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.collectives import WirelessRound, wireless_psum
from ..models import api
from ..models.transformer import Transformer
from ..optim.sgd import SGDConfig, sgd_update
from .mesh import client_axes, n_clients
from .sharding import ShardingRules, batch_axes, cache_axes, decode_rules


@dataclasses.dataclass
class StepBundle:
    name: str
    fn: Callable
    in_shardings: Any
    out_shardings: Any
    abstract_inputs: tuple     # positional args as ShapeDtypeStructs

    def lower(self):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings)
        return jitted.lower(*self.abstract_inputs)


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ------------------------------------------------------------- train step

def fl_round_arrays(mesh: Mesh, *, gammas=None, chis=None, nus=None,
                    alpha: float = 1.0, noise_scale: float = 0.0,
                    levels: float = 255.0):
    """Build the per-round FL arrays shaped like the client mesh axes.

    Defaults give an ideal round (all participate, weight 1).
    """
    caxes = client_axes(mesh)
    shape = tuple(mesh.shape[a] for a in caxes)
    n = int(np.prod(shape))
    if gammas is None:
        gammas = np.ones(n)
    if chis is None:
        chis = np.ones(n)
    if nus is None:
        nus = np.ones(n)
    weight = (np.asarray(chis) * np.asarray(gammas)
              / np.asarray(nus)).reshape(shape)
    return {
        "weight": jnp.asarray(weight, jnp.float32),
        "alpha": jnp.asarray(alpha, jnp.float32),
        "noise_scale": jnp.asarray(noise_scale, jnp.float32),
        "levels": jnp.full(shape, levels, jnp.float32),
    }


def _restrict_spec(spec: P, manual: tuple) -> P:
    """Keep only manual-axis entries of a PartitionSpec (auto axes dropped)."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in manual)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in manual else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def make_train_step(model: Transformer, mesh: Mesh, *,
                    aggregator: str = "ota",
                    sgd: SGDConfig = SGDConfig(eta=1e-2),
                    batch: int = 8, seq: int = 128,
                    rules: Optional[ShardingRules] = None,
                    flags: Optional[dict] = None,
                    use_kernel: bool = True) -> StepBundle:
    cfg = model.cfg
    rules = rules or ShardingRules.default()
    caxes = client_axes(mesh)
    nc = n_clients(mesh)
    flags = dict(flags or {})
    flags.setdefault("mesh", mesh)
    expert_parallel = flags.get("moe_impl") == "ep"
    if expert_parallel:
        flags["_in_manual"] = True      # model runs inside client shard_map

    aparams = model.abstract_params()
    pspecs = rules.tree_specs(mesh, aparams, model.axes)
    # Params enter the client-manual shard_map replicated over client axes
    # (every FL client holds the full model), EXCEPT expert-parallel
    # weights in "ep" mode: those stay manual-sharded over "data" and their
    # gradients are globally aggregated by the backward all_to_all already.
    if expert_parallel:
        pspecs_manual = jax.tree.map(lambda s: _restrict_spec(s, caxes),
                                     pspecs, is_leaf=lambda x: isinstance(x, P))
    else:
        pspecs_manual = jax.tree.map(lambda s: P(), pspecs,
                                     is_leaf=lambda x: isinstance(x, P))
    skip_psum = jax.tree.map(lambda s: len(s) > 0, pspecs_manual,
                             is_leaf=lambda x: isinstance(x, P))
    abatch = api.batch_spec(cfg, batch, seq)
    bspecs = rules.tree_specs(mesh, abatch, batch_axes(abatch))
    caxes_shape = tuple(mesh.shape[a] for a in caxes)
    fl_specs = {
        "weight": P(*caxes),
        "alpha": P(),
        "noise_scale": P(),
        "levels": P(*caxes),
    }
    afl = {
        "weight": jax.ShapeDtypeStruct(caxes_shape, jnp.float32),
        "alpha": jax.ShapeDtypeStruct((), jnp.float32),
        "noise_scale": jax.ShapeDtypeStruct((), jnp.float32),
        "levels": jax.ShapeDtypeStruct(caxes_shape, jnp.float32),
    }
    akey = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)

    # inside the body, batch leaves keep only their non-client dims sharded;
    # manual axes are stripped from the body-visible specs automatically
    def body(params, batch_in, fl, key):
        w_client = fl["weight"].reshape(())

        def local_loss(p):
            loss, metrics = api.loss_fn(model, p, batch_in, flags)
            # per-client wireless weight applied to the LOSS: grad is
            # linear, so grad(w*loss) = w*grad — and this stays correct
            # when expert-parallel routing spreads a client's tokens
            # across expert shards (the weight follows the tokens).
            return loss * w_client.astype(loss.dtype), loss

        (_, loss), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params)
        rinfo = WirelessRound(weight=jnp.ones(()), alpha=fl["alpha"],
                              noise_scale=fl["noise_scale"],
                              levels=fl["levels"])
        ghat = wireless_psum(grads, rinfo, caxes, key, mode=aggregator,
                             use_kernel=use_kernel, skip_psum=skip_psum)
        new_params, _ = sgd_update(sgd, params, ghat,
                                   jax.tree.map(jnp.zeros_like, params))
        loss_mean = jax.lax.psum(loss, caxes) / nc
        return new_params, loss_mean

    shard_body = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs_manual, bspecs, fl_specs, P()),
        out_specs=(pspecs_manual, P()),
        axis_names=set(caxes), check_vma=False)

    in_sh = (_named(mesh, pspecs), _named(mesh, bspecs),
             _named(mesh, fl_specs), NamedSharding(mesh, P()))
    out_sh = (_named(mesh, pspecs), NamedSharding(mesh, P()))
    return StepBundle(
        name=f"train[{aggregator}]", fn=shard_body,
        in_shardings=in_sh, out_shardings=out_sh,
        abstract_inputs=(aparams, abatch, afl, akey))


# ------------------------------------------------------------ serve steps

def make_prefill_step(model: Transformer, mesh: Mesh, *, batch: int,
                      seq: int, cache_len: Optional[int] = None,
                      rules: Optional[ShardingRules] = None,
                      flags: Optional[dict] = None) -> StepBundle:
    cfg = model.cfg
    seq = api.effective_seq(cfg, seq)
    cache_len = cache_len or seq
    rules = rules or decode_rules(batch, mesh)
    flags = dict(flags or {})
    flags.setdefault("mesh", mesh)
    aparams = model.abstract_params()
    pspecs = rules.tree_specs(mesh, aparams, model.axes)
    abatch = api.batch_spec(cfg, batch, seq)
    bspecs = rules.tree_specs(mesh, abatch, batch_axes(abatch))

    def fn(params, batch_in):
        logits, caches, memory = api.prefill(model, params, batch_in,
                                             cache_len, flags)
        return logits, caches, memory

    acaches = jax.eval_shape(
        lambda: model.init_cache(batch, cache_len, dtype=cfg.dtype))
    cspecs = rules.tree_specs(mesh, acaches, cache_axes(acaches))
    batch_axes_tuple = (("pod", "data") if "pod" in mesh.axis_names
                        else ("data",))
    logit_spec = (P(batch_axes_tuple) if batch % n_clients(mesh) == 0
                  else P())
    mem_spec = (rules.spec_for(mesh, (batch, cfg.encoder_positions,
                                      cfg.d_model),
                               ("batch", "enc_seq", "embed"))
                if cfg.arch_type == "audio" else P())
    in_sh = (_named(mesh, pspecs), _named(mesh, bspecs))
    out_sh = (NamedSharding(mesh, logit_spec), _named(mesh, cspecs),
              NamedSharding(mesh, mem_spec))
    return StepBundle("prefill", fn, in_sh, out_sh, (aparams, abatch))


def make_decode_step(model: Transformer, mesh: Mesh, *, batch: int,
                     cache_len: int,
                     rules: Optional[ShardingRules] = None,
                     flags: Optional[dict] = None) -> StepBundle:
    cfg = model.cfg
    rules = rules or decode_rules(batch, mesh)
    flags = dict(flags or {})
    flags.setdefault("mesh", mesh)
    aparams = model.abstract_params()
    pspecs = rules.tree_specs(mesh, aparams, model.axes)
    acaches = jax.eval_shape(
        lambda: model.init_cache(batch, cache_len, dtype=cfg.dtype))
    cspecs = rules.tree_specs(mesh, acaches, cache_axes(acaches))
    batch_shardable = batch % n_clients(mesh) == 0
    bspec = (P(("pod", "data") if "pod" in mesh.axis_names else ("data",))
             if batch_shardable else P())

    def fn(params, token, position, caches, memory):
        logits, new_caches = api.decode_step(model, params, token, position,
                                             caches, memory=memory,
                                             flags=flags)
        return logits, new_caches

    atok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    apos = jax.ShapeDtypeStruct((batch,), jnp.int32)
    amem = (jax.ShapeDtypeStruct((batch, cfg.encoder_positions, cfg.d_model),
                                 cfg.dtype)
            if cfg.arch_type == "audio" else None)
    mem_spec = (rules.spec_for(mesh, (batch, cfg.encoder_positions,
                                      cfg.d_model),
                               ("batch", "enc_seq", "embed"))
                if cfg.arch_type == "audio" else P())
    in_sh = (_named(mesh, pspecs), NamedSharding(mesh, bspec),
             NamedSharding(mesh, bspec), _named(mesh, cspecs),
             NamedSharding(mesh, mem_spec))
    out_sh = (NamedSharding(mesh, bspec), _named(mesh, cspecs))
    return StepBundle("decode", fn, in_sh, out_sh,
                      (aparams, atok, apos, acaches, amem))
