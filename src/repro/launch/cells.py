"""Cell builders: one (arch × shape × mesh) dry-run unit.

A *cell* bundles the step callable, its abstract (never-allocated) inputs
with resolved shardings, donation indices, and the trip counts the
finite-difference cost model needs (launch/costing.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeConfig, SHAPES, cell_is_runnable
from ..configs.registry import get_config, input_specs
from ..models.model import LModel
from ..models.param import abstract
from ..sharding import partition as ps
from ..train import optimizer as O
from ..train.train_loop import make_train_step


@dataclasses.dataclass
class Cell:
    arch: str
    shape: ShapeConfig
    fn: Callable
    args: tuple
    donate: tuple[int, ...]
    # trip counts for cost extrapolation
    n_cycles: int              # pattern cycles in the full model
    rem_layers: int
    pattern_len: int
    n_micro: int               # train: grad-accum microbatches (else 1)
    model_flops: float         # analytic 6·N_active·D (train) / 2·N_active·D
    params_count: int
    active_params: int


def opt_config(cfg: ArchConfig) -> O.OptConfig:
    return O.OptConfig(state_dtype=cfg.opt_state_dtype,
                       algorithm=cfg.optimizer)


def rules_for(kind: str, overrides: dict | None = None) -> dict:
    base = {"train": ps.TRAIN_RULES, "prefill": ps.PREFILL_RULES,
            "decode": ps.DECODE_RULES}[kind]
    out = dict(base)
    if overrides:
        out.update(overrides)
    return out


# ---------------------------------------------------------------------------
# analytic parameter / FLOP counts
# ---------------------------------------------------------------------------


def param_counts(cfg: ArchConfig) -> tuple[int, int]:
    """(total, active-per-token) non-embedding parameter counts."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    per_layer_attn = (D * cfg.n_heads * cfg.head_dim          # wq
                      + 2 * D * cfg.n_kv_heads * cfg.head_dim  # wk, wv
                      + cfg.n_heads * cfg.head_dim * D)        # wo
    n_mats = 3 if cfg.mlp_gated else 2
    per_layer_mlp = n_mats * D * F
    total = active = 0
    for i in range(cfg.n_layers):
        kind = cfg.layer_kind(i)
        if kind in ("global", "local"):
            total += per_layer_attn
            active += per_layer_attn
        elif kind == "rglru":
            di = cfg.d_inner
            w = 2 * D * di + 2 * di * di + di * D + cfg.d_conv * di
            total += w
            active += w
        elif kind == "mamba":
            di, N, R = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
            w = (D * 2 * di + cfg.d_conv * di + di * (R + 2 * N)
                 + R * di + di * D)
            total += w
            active += w
        if cfg.d_ff > 0 and kind != "mamba":
            if E:
                total += E * per_layer_mlp + D * E
                active += cfg.moe_topk * per_layer_mlp + D * E
            else:
                total += per_layer_mlp
                active += per_layer_mlp
    if cfg.enc_dec:
        enc = cfg.n_enc_layers * (per_layer_attn + per_layer_mlp)
        xattn = cfg.n_layers * per_layer_attn
        total += enc + xattn
        active += enc + xattn
    return total, active


def analytic_model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    _, active = param_counts(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * active * tokens


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------


def _max_seq(cfg: ArchConfig, shape: ShapeConfig) -> int:
    return shape.seq_len if cfg.pos_emb == "learned" else 0


def build_cell(arch: str, shape_name: str, mesh, *,
               rule_overrides: dict | None = None,
               cfg_override: ArchConfig | None = None) -> Cell:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        raise ValueError(f"skip: {why}")
    rules = rules_for(shape.kind, rule_overrides)
    model = LModel(cfg, max_seq=_max_seq(cfg, shape))
    # train/prefill: FSDP (per-layer gathers amortize over the batch);
    # decode: no FSDP — weights get 2D TP via DECODE_RULES' embed→data
    params = model.abstract_params(
        mesh, rules, fsdp=(shape.kind in ("train", "prefill")))
    total, active = param_counts(cfg)
    mflops = analytic_model_flops(cfg, shape)
    common = dict(arch=arch, shape=shape, pattern_len=len(cfg.attn_pattern),
                  n_cycles=cfg.n_pattern_groups,
                  rem_layers=cfg.n_remainder_layers,
                  model_flops=mflops, params_count=total,
                  active_params=active)

    if shape.kind == "train":
        ocfg = opt_config(cfg)
        opt_state = O.abstract_state(ocfg, params)
        batch = input_specs(cfg, shape, mesh, rules)
        from ..models.param import specs as param_specs
        gspecs = param_specs(model.param_specs(), mesh, rules, fsdp=True)
        step = make_train_step(model, ocfg, grad_specs=gspecs)
        n_micro = max(1, shape.global_batch // cfg.microbatch_seqs)
        return Cell(fn=step, args=(params, opt_state, batch),
                    donate=(0, 1), n_micro=n_micro, **common)

    B, S = shape.global_batch, shape.seq_len
    cdt = jnp.bfloat16
    if shape.kind == "prefill":
        cross = S if cfg.enc_dec else 0
        cache = abstract(model.cache_specs(B, S, cdt, cross_len=cross),
                         mesh, rules, fsdp=False)
        batch = input_specs(cfg, shape, mesh, rules)

        if cfg.enc_dec:
            def fn(params, tokens, enc_inputs, cache):
                cache = model.build_cross_caches(params, cache, enc_inputs)
                return model.prefill(params, tokens, cache,
                                     chunk=cfg.prefill_chunk)
            args = (params, batch["tokens"], batch["enc_inputs"], cache)
            donate = (3,)
        else:
            def fn(params, tokens, cache):
                return model.prefill(params, tokens, cache,
                                     chunk=cfg.prefill_chunk)
            args = (params, batch["tokens"], cache)
            donate = (2,)
        return Cell(fn=fn, args=args, donate=donate, n_micro=1, **common)

    # decode
    cross = cfg.enc_len_decode if cfg.enc_dec else 0
    cache = abstract(model.cache_specs(B, S, cdt, cross_len=cross),
                     mesh, rules, fsdp=False)
    batch = input_specs(cfg, shape, mesh, rules)

    def fn(params, tokens_t, cache):
        logits, cache = model.decode_step(params, tokens_t, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    return Cell(fn=fn, args=(params, batch["tokens_t"], cache),
                donate=(2,), n_micro=1, **common)


def lower_cell(cell: Cell, mesh):
    with jax.set_mesh(mesh):
        return jax.jit(cell.fn, donate_argnums=cell.donate).lower(*cell.args)
