"""LM substrate micro-benchmark: smoke-scale train/decode step throughput
on the host CPU (substrate health; not a paper table)."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models.model import LModel
from repro.serve.decode import make_serve_fns
from repro.train import optimizer as O
from repro.train.train_loop import make_train_step


def run() -> list[tuple[str, float, str]]:
    rows = []
    for arch in ("qwen3-8b", "falcon-mamba-7b", "grok-1-314b"):
        cfg = smoke_config(arch)
        model = LModel(cfg, max_seq=64)
        params = model.init(jax.random.key(0))
        B, S = 4, 32
        batch = {
            "tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32),
        }
        if cfg.enc_dec:
            batch["enc_inputs"] = jnp.zeros((B, S, cfg.d_model), jnp.bfloat16)
        ocfg = O.OptConfig(algorithm=cfg.optimizer,
                           state_dtype=cfg.opt_state_dtype)
        state = O.init_state(ocfg, params)
        step = jax.jit(make_train_step(model, ocfg))
        params, state, _ = step(params, state, batch)  # warm
        t0 = time.perf_counter()
        reps = 10
        for _ in range(reps):
            params, state, m = step(params, state, batch)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / reps
        rows.append((f"lm_step/{arch}/train_us", dt * 1e6,
                     f"{B * S / dt:.0f}tok/s"))

        # decode
        _, serve_step = make_serve_fns(model)
        cache = model.init_cache(B, 64)
        toks = jnp.ones((B, 1), jnp.int32)
        nxt, _, cache = serve_step(params, toks, cache)  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            nxt, _, cache = serve_step(params, nxt, cache)
        jax.block_until_ready(nxt)
        dt = (time.perf_counter() - t0) / reps
        rows.append((f"lm_step/{arch}/decode_us", dt * 1e6,
                     f"{B / dt:.0f}tok/s"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
