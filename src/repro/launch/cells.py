"""(architecture x shape x mesh) cell construction for the dry-run.

``build_cell`` returns a jitted entry point plus ShapeDtypeStruct arguments
(with NamedShardings attached): ``.lower(*args).compile()`` is the dry-run.
No parameters or activations are ever materialized.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, SHAPES
from repro.core.comm import CommConfig
from repro.models import transformer as tf
from repro.models.common import ModelConfig
from repro.training import optimizer as opt
from repro.training.train_step import TrainState, train_step_fn, state_specs


@dataclass
class Cell:
    arch: str
    shape: str
    fn: Any                  # jitted callable
    args: tuple              # ShapeDtypeStructs (sharded)
    meta: dict


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _tree_sds(tree_shapes, tree_specs, mesh):
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp),
        tree_shapes, tree_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _dp_spec(mesh, batch=None):
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if batch is not None:
        n = 1
        for a in dp:
            n *= mesh.shape[a]
        if batch % n != 0:
            return ()          # replicate tiny batches (e.g. long_500k B=1)
    return dp


def model_flops(cfg: ModelConfig, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); decode uses the
    2 N per-token forward cost."""
    n = _active_params(cfg)
    per_tok = 6.0 * n if kind == "train" else 2.0 * n
    return per_tok * tokens


def _active_params(cfg: ModelConfig) -> float:
    """Active (per-token) parameter count."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    emb = V * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "ssm":
        s = cfg.ssm
        din = s.d_inner(d)
        nh = s.n_heads(d)
        per = d * (2 * din + 2 * s.d_state + nh) + din * d
        return emb + L * per
    att = d * cfg.n_heads * cfg.d_head * 2 + \
        d * cfg.n_kv * cfg.d_head * 2
    gate = 1 if cfg.act in ("swiglu", "geglu") else 0
    mlp = d * cfg.d_ff * (2 + gate)
    if cfg.family == "moe":
        mlp = mlp * cfg.moe.top_k + d * cfg.moe.n_experts  # router
    per = att + mlp
    if cfg.family == "hybrid":
        dr = cfg.hybrid.d_rnn or d
        rec = d * dr * 2 + dr * dr * 2 + dr * d + d * cfg.d_ff * (2 + gate)
        n_att = cfg.n_layers // 3
        return emb + n_att * per + (L - n_att) * rec
    if cfg.family == "encdec":
        return emb + L * (per + att) + cfg.n_enc_layers * per
    return emb + L * per


def build_cell(arch: str, shape_name: str, mesh,
               comm: CommConfig = CommConfig(),
               adam: opt.AdamWConfig | None = None,
               remat: str | None = None,
               extra_cfg: dict | None = None) -> Cell:
    if arch == "flups-poisson":
        return _build_poisson_cell(shape_name, mesh, comm)
    cfg = get_config(arch)
    if remat is not None:
        import dataclasses as dc
        cfg = dc.replace(cfg, remat=remat)
    if extra_cfg:
        import dataclasses as dc
        cfg = dc.replace(cfg, **extra_cfg)
    sh = SHAPES[shape_name]
    ms = dict(mesh.shape)
    B, S = sh.global_batch, sh.seq_len
    dp = _dp_spec(mesh, B)
    dtype_tok = jnp.int32

    pspecs = tf.param_specs(cfg, ms)
    pshapes = jax.eval_shape(partial(tf.init_params, cfg=cfg),
                             jax.random.PRNGKey(0))
    meta = {"arch": arch, "shape": shape_name, "kind": sh.kind,
            "global_batch": B, "seq_len": S,
            "mesh": tuple(mesh.shape.items()),
            "model_flops": model_flops(
                cfg, B * S if sh.kind != "decode" else B, sh.kind)}

    if sh.kind == "train":
        adam = adam or opt.AdamWConfig()
        sspec = state_specs(cfg, ms)
        sshapes = jax.eval_shape(
            lambda k: TrainState(tf.init_params(k, cfg),
                                 opt.init_opt_state(
                                     tf.init_params(k, cfg)), None),
            jax.random.PRNGKey(0))
        state_sds = _tree_sds(sshapes, sspec, mesh)
        batch = {"inputs": _sds((B, S), dtype_tok, mesh, P(dp, None)),
                 "labels": _sds((B, S), dtype_tok, mesh, P(dp, None)),
                 "mask": _sds((B, S), jnp.float32, mesh, P(dp, None))}
        if cfg.n_frontend_tokens:
            batch["frontend"] = _sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                     jnp.float32, mesh, P(dp, None, None))
        step = train_step_fn(cfg, adam=adam, comm=comm, mesh=mesh)
        fn = jax.jit(step, donate_argnums=(0,))
        return Cell(arch, shape_name, fn, (state_sds, batch), meta)

    params_sds = _tree_sds(pshapes, pspecs, mesh)

    if sh.kind == "prefill":
        tokens = _sds((B, S), dtype_tok, mesh, P(dp, None))
        args = [params_sds, tokens]
        if cfg.n_frontend_tokens:
            args.append(_sds((B, cfg.n_frontend_tokens, cfg.d_model),
                             jnp.float32, mesh, P(dp, None, None)))

            def fwd(p, t, f):
                return tf.forward(p, cfg, t, f, comm, mesh)
        else:
            def fwd(p, t):
                return tf.forward(p, cfg, t, None, comm, mesh)
        return Cell(arch, shape_name, jax.jit(fwd), tuple(args), meta)

    # decode: one new token with caches of length S
    cshapes = jax.eval_shape(partial(tf.init_caches, cfg, B, S))
    cspecs = tf.cache_specs(cfg, ms, cshapes, dp=dp)
    caches_sds = _tree_sds(cshapes, cspecs, mesh)
    token = _sds((B, 1), dtype_tok, mesh, P(dp, None))
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def dec(p, t, c, pos):
        return tf.decode_step(p, cfg, t, c, pos, comm, mesh)

    fn = jax.jit(dec, donate_argnums=(2,))
    return Cell(arch, shape_name, fn, (params_sds, token, caches_sds, pos),
                meta)


def _build_poisson_cell(shape_name, mesh, comm):
    from repro.core.comm import autotune_candidates
    from repro.configs.flups_poisson import CONFIG
    from repro.core.solver import get_solver
    multi = "pod" in mesh.shape
    # precedence: a launcher comm that differs from the stock default wins;
    # otherwise the arch config's knobs apply (comm="auto" = plan-time
    # tuner, a capability the dryrun CLI cannot express)
    if comm == CommConfig():
        comm = ("auto" if CONFIG.comm == "auto"
                else CommConfig(CONFIG.comm, CONFIG.comm_chunks))
    # single-pod meshes run CONFIG.batch fields as ONE batched multi-RHS
    # solve (in-block batch axis); multi-pod shards the batch over "pod"
    local_batch = not multi and CONFIG.batch > 1
    batch = CONFIG.batch if (multi or local_batch) else None
    # the global plan cache makes cell re-construction (repeated dryruns
    # over the same mesh) hit one live solver instance
    solver = get_solver(
        (CONFIG.n,) * 3, 1.0, CONFIG.bcs, layout=CONFIG.layout,
        green_kind=CONFIG.green, mesh=mesh,
        axes=("data", "model"), comm=comm,
        batch_axis="pod" if multi else None, lazy_green=True,
        engine=CONFIG.engine, doubling=CONFIG.doubling,
        relayout=CONFIG.relayout,
        # guided search derives its own predictor-ranked shortlist from the
        # solver's plan; only brute mode pins the exhaustive candidate grid
        autotune_search=CONFIG.comm_autotune_search,
        autotune_candidates=(None if CONFIG.comm_autotune_search == "guided"
                             else autotune_candidates(
            CONFIG.comm_autotune_max_chunks,
            folds=(("pack", "unpack") if CONFIG.relayout == "scheduled"
                   else ("pack",)))),
        autotune_cache=CONFIG.comm_autotune_cache or None,
        autotune_budget=CONFIG.comm_autotune_budget_s or None,
        # comm="auto" must time the rank it will run: the in-block batch
        autotune_batch=CONFIG.batch if local_batch else None,
        verify=CONFIG.verify or None, verify_rtol=CONFIG.verify_rtol)
    f_sds = jax.ShapeDtypeStruct(
        solver.padded_input_shape(batch), jnp.float32,
        sharding=NamedSharding(mesh, solver.input_spec(local_batch)))
    g_sds = jax.ShapeDtypeStruct(
        solver._green_np.shape, solver._green_np.dtype,
        sharding=NamedSharding(mesh, solver.g_spec))
    n = CONFIG.n
    meta = {"arch": "flups-poisson", "shape": shape_name, "kind": "solve",
            "grid": n, "mesh": tuple(mesh.shape.items()),
            "batch": batch or 1,
            # forward + backward 3-D FFT on the doubled (2n)^3 domain
            "model_flops": (batch or 1) * 2 * 5 * (2 * n) ** 3
            * np.log2((2 * n) ** 3)}
    return Cell("flups-poisson", shape_name, solver.jit_for(local_batch),
                (f_sds, g_sds), meta)
