"""Finite-difference roofline cost model (see DESIGN.md §5).

``cost_analysis()`` on a scanned module counts each ``while`` body once and
reports per-device numbers, so totals cannot be read off the production
compile. Instead we compile *unrolled* variants of the same step at
n_layers = 2·|pattern| and 3·|pattern| cycles (full width, full batch, one
microbatch) and extrapolate:

    marginal_per_cycle = cost(3) − cost(2)
    per_microbatch     = cost(2) + (cycles_real − 2)·marginal
                         + rem_layers·(marginal/|pattern|)
    step_total         = n_micro · per_microbatch (+ optimizer, train only)

The FD unit for training is one microbatch's ``value_and_grad`` — so
FSDP param re-gathers are counted once *per microbatch*, exactly as the real
step executes them. Collective bytes are parsed from the unrolled HLO
(result-shape bytes for all-gather, operand bytes otherwise; per-device).
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, ShapeConfig, SHAPES
from ..configs.registry import get_config, input_specs
from ..models.model import LModel
from ..models.param import abstract
from ..train import optimizer as O
from . import cells as C
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS

_COLL_RE = re.compile(
    r"%(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[\w.-]*\s*=\s*\(?(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes(hlo_text: str) -> tuple[float, dict[str, int]]:
    """(per-device collective bytes, op counts) from optimized HLO text."""
    total = 0.0
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, dt, dims = m.groups()
        counts[op] = counts.get(op, 0) + 1
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total, counts


@dataclasses.dataclass
class CostReport:
    flops_dev: float           # per-device, full step
    bytes_dev: float
    coll_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_total: float
    useful_ratio: float
    fd_compile_s: float
    counts: dict[str, int]

    def terms(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def _fd_cfg(cfg: ArchConfig, n_cycles: int) -> ArchConfig:
    import dataclasses as dc
    pl = len(cfg.attn_pattern)
    return dc.replace(
        cfg,
        n_layers=n_cycles * pl,
        n_enc_layers=n_cycles if cfg.enc_dec else 0,
        unroll_groups=True,
        loss_chunks=1,
        prefill_chunk=10**9,     # single-chunk prefill (no seq scan)
    )


def _measure(fn, args, mesh, donate=()) -> tuple[dict, float, dict]:
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    dt = time.perf_counter() - t0
    ca = compiled.cost_analysis() or {}
    coll, counts = collective_bytes(compiled.as_text())
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": coll,
    }
    del compiled
    return out, dt, counts


def _build_fd_step(cfg: ArchConfig, shape: ShapeConfig, mesh, rules):
    """One-microbatch unit step for the FD measurements."""
    model = LModel(cfg, max_seq=shape.seq_len if cfg.pos_emb == "learned"
                   else 0)
    params = model.abstract_params(
        mesh, rules, fsdp=(shape.kind in ("train", "prefill")))
    if shape.kind == "train":
        # one microbatch of the real step (grad accumulation trips = n_micro)
        import dataclasses as dc
        mb = min(cfg.microbatch_seqs, shape.global_batch)
        mb_shape = dc.replace(shape, global_batch=mb)
        batch = input_specs(cfg, mb_shape, mesh, rules)

        def fn(params, batch):
            return jax.value_and_grad(model.loss_fn)(params, batch)

        return fn, (params, batch), ()
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "prefill":
        cross = S if cfg.enc_dec else 0
        cache = abstract(model.cache_specs(B, S, jnp.bfloat16,
                                           cross_len=cross),
                         mesh, rules, fsdp=False)
        batch = input_specs(cfg, shape, mesh, rules)
        if cfg.enc_dec:
            def fn(params, tokens, enc, cache):
                cache = model.build_cross_caches(params, cache, enc)
                return model.prefill(params, tokens, cache,
                                     chunk=cfg.prefill_chunk)
            return fn, (params, batch["tokens"], batch["enc_inputs"],
                        cache), (3,)
        def fn(params, tokens, cache):
            return model.prefill(params, tokens, cache,
                                 chunk=cfg.prefill_chunk)
        return fn, (params, batch["tokens"], cache), (2,)
    cross = cfg.enc_len_decode if cfg.enc_dec else 0
    cache = abstract(model.cache_specs(B, S, jnp.bfloat16, cross_len=cross),
                     mesh, rules, fsdp=False)
    batch = input_specs(cfg, shape, mesh, rules)

    def fn(params, tokens_t, cache):
        return model.decode_step(params, tokens_t, cache)

    return fn, (params, batch["tokens_t"], cache), (2,)


def _optimizer_cost(cfg: ArchConfig, mesh, rules) -> dict:
    """AdamW update over the REAL-size param tree (elementwise, no loops)."""
    model = LModel(cfg, max_seq=1 if cfg.pos_emb == "learned" else 0)
    params = model.abstract_params(mesh, rules, fsdp=True)
    ocfg = C.opt_config(cfg)
    state = O.abstract_state(ocfg, params)
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(
            p.shape, jnp.dtype(cfg.grad_accum_dtype), sharding=p.sharding),
        params)

    def fn(params, grads, state):
        return O.update(ocfg, params, grads, state)

    out, dt, counts = _measure(fn, (params, grads, state), mesh,
                               donate=(0, 2))
    return out


def cost_model(arch: str, shape_name: str, mesh, *,
               rule_overrides: dict | None = None,
               cfg_override: ArchConfig | None = None) -> CostReport:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    rules = C.rules_for(shape.kind, rule_overrides)
    pl = len(cfg.attn_pattern)
    cycles_real = cfg.n_pattern_groups
    rem = cfg.n_remainder_layers
    n_micro = (max(1, shape.global_batch // cfg.microbatch_seqs)
               if shape.kind == "train" else 1)

    t0 = time.perf_counter()
    fn2, args2, d2 = _build_fd_step(_fd_cfg(cfg, 2), shape, mesh, rules)
    c2, _, counts2 = _measure(fn2, args2, mesh, d2)
    fn3, args3, d3 = _build_fd_step(_fd_cfg(cfg, 3), shape, mesh, rules)
    c3, _, counts3 = _measure(fn3, args3, mesh, d3)

    def total(key):
        marg = max(c3[key] - c2[key], 0.0)
        per_mb = c2[key] + (cycles_real - 2) * marg + rem * (marg / pl)
        return n_micro * per_mb

    flops_dev = total("flops")
    bytes_dev = total("bytes")
    coll_dev = total("coll")
    if shape.kind == "train":
        oc = _optimizer_cost(cfg, mesh, rules)
        flops_dev += oc["flops"]
        bytes_dev += oc["bytes"]
        coll_dev += oc["coll"]
    fd_s = time.perf_counter() - t0

    chips = int(np.prod(mesh.devices.shape))
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    model_flops = C.analytic_model_flops(cfg, shape)
    hlo_total = flops_dev * chips
    return CostReport(
        flops_dev=flops_dev, bytes_dev=bytes_dev, coll_dev=coll_dev,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops,
        hlo_flops_total=hlo_total,
        useful_ratio=model_flops / max(hlo_total, 1.0),
        fd_compile_s=fd_s,
        counts={k: counts2.get(k, 0) + counts3.get(k, 0)
                for k in set(counts2) | set(counts3)},
    )
