#!/usr/bin/env python
"""Locate where the BERT-base train step spends its time.

Breaks the step into components by timing ablations on the chip, and
quantifies the per-dispatch overhead by sweeping the scan window.  Each
line printed is one JSON record.  Run it as the ONLY process on the chip
(one process holds a chip at a time).  It has not produced a row on the
installed JAX (ROADMAP.md S3).

Ablations (all bf16, batch 64, seq 128, adamw).  Every arm runs the
SHIPPING flagship config — XLA dense attention (flash_min_seq=4096 keeps
the kernel out at S=128):
  full            — the benchmarked step (XLA attn, packed head, dense CE)
  no_dropout      — train step with dropout 0.0 (isolates threefry+mask cost)
  flash_attn      — the Pallas-kernel contrast arm (use_flash=True)
  fwd_only        — loss forward, no grad/optimizer
  encoder_only    — encoder forward, no head/loss
  no_opt          — grads but apply zero update (isolates adamw elementwise)
"""

from __future__ import annotations

import dataclasses as dc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mpi_tensorflow_tpu.data import synthetic
from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.parallel import mesh as meshlib
from mpi_tensorflow_tpu.train import gspmd

B, S = 64, 128


def median_dispatch(fn, *args, iters=10, warmup=2, thread_state=False):
    """Median seconds per dispatch; value-fetch is the sync point.

    ``thread_state``: the first positional arg is a donated train state and
    ``fn`` returns ``(new_state, aux)`` — each call must consume the
    PREVIOUS call's output state (the donated input buffers are dead)."""
    def call(args):
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[-1]).ravel()[:1]   # sync fetch
        if thread_state:
            return (out[0],) + tuple(args[1:])
        return args

    for _ in range(warmup):
        args = call(args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        args = call(args)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def make_inputs(K):
    toks, tgts, mask = synthetic.mlm_batches(K * B, seq_len=S,
                                             vocab_size=30522, seed=0)
    shape = (K, B, S)
    return ({"tokens": jnp.asarray(toks.reshape(shape)),
             "mask": jnp.asarray(mask.reshape(shape))},
            jnp.asarray(tgts.reshape(shape)))


def build(dropout=0.1, use_flash=False, fused_qkv=False):
    mesh = meshlib.make_mesh()
    # flash_min_seq=0 keeps the use_flash contrast meaningful at S=128:
    # True = forced kernel (the contrast arm), False = XLA dense — the
    # shipping default AND this script's default, so every downstream
    # ablation (fwd_only/encoder_only/no_opt reuse the section-1 model)
    # diagnoses the flagship path
    cfg = dc.replace(bert.BERT_BASE, dtype=jnp.bfloat16, dropout=dropout,
                     fused_qkv=fused_qkv, flash_min_seq=0)
    model = bert.BertMlm(cfg, mesh=mesh, use_flash=use_flash)
    tx = optax.adamw(1e-4)
    state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh)
    return model, mesh, tx, state


def emit(name, sec_per_step, extra=None):
    rec = {"ablation": name, "step_ms": round(sec_per_step * 1e3, 3),
           "tok_per_sec": round(B * S / sec_per_step, 1)}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)


def main():
    # 1. scan-window sweep on the full step: separates device step time
    #    from per-dispatch overhead.  dispatch(K) = K*step + C
    # (each emit doubles as a progress marker: on a timeout the partial
    # stdout names the last completed stage)
    print(json.dumps({"stage": "client_init"}), flush=True)
    model, mesh, tx, state0 = build()
    print(json.dumps({"stage": "built"}), flush=True)

    def fresh():
        """Deep on-device copy — donated timings consume the copy, the
        pristine state stays alive for later ablations."""
        return jax.tree.map(lambda x: x + 0 if hasattr(x, "dtype") else x,
                            state0)

    multi0 = gspmd.make_gspmd_multi_step(model, mesh, tx)
    # two points determine the dispatch(K) = K*step + C line; each extra K
    # is another ~2min remote compile and the 1500s budget timed out once
    for K in (1, 32):
        batches, labels = make_inputs(K)
        sec = median_dispatch(multi0, fresh(), batches, labels,
                              jax.random.key(1), thread_state=True)
        emit(f"full_scan{K}", sec / K, {"dispatch_ms": round(sec * 1e3, 2),
                                        "K": K})

    # 2. no-dropout ablation
    model_nd, mesh, tx, state = build(dropout=0.0)
    multi = gspmd.make_gspmd_multi_step(model_nd, mesh, tx)
    batches, labels = make_inputs(16)
    sec = median_dispatch(multi, state, batches, labels, jax.random.key(1),
                          thread_state=True)
    emit("no_dropout_scan16", sec / 16)

    # 3. flash-kernel contrast arm (the retired variant; the default
    # everywhere else in this script is the shipping XLA path)
    model_x, mesh, tx, state = build(use_flash=True)
    multi = gspmd.make_gspmd_multi_step(model_x, mesh, tx)
    sec = median_dispatch(multi, state, batches, labels, jax.random.key(1),
                          thread_state=True)
    emit("flash_attn_scan16", sec / 16)

    # (fused-QKV and rbg-PRNG candidates moved to BENCH-grade queue arms
    # bert_fused_qkv / bert_rbg — each ablation here costs a ~2min remote
    # compile and the 1500s window budget timed out once)

    # 4. forward-only loss (scan to amortize) — pristine state0 params
    params0 = state0.params

    @jax.jit
    def fwd_multi(params, batches, labels, rng):
        def body(c, xs):
            b, l = xs
            loss, _ = model.loss(params, None, b, l, rng=rng, train=True)
            return c + loss, None
        return jax.lax.scan(body, jnp.zeros(()), (batches, labels))[0]

    sec = median_dispatch(fwd_multi, params0, batches, labels,
                          jax.random.key(1))
    emit("fwd_only_scan16", sec / 16)

    # 5. encoder-only forward
    @jax.jit
    def enc_multi(params, batches, rng):
        def body(c, b):
            h = model.encode(params, b["tokens"], train=True, rng=rng)
            return c + jnp.sum(h.astype(jnp.float32)), None
        return jax.lax.scan(body, jnp.zeros(()), batches)[0]

    sec = median_dispatch(enc_multi, params0, batches, jax.random.key(1))
    emit("encoder_fwd_only_scan16", sec / 16)

    # 6. grads but no optimizer update (isolate adamw elementwise+state IO)
    @jax.jit
    def grad_multi(state, batches, labels, rng):
        def body(s, xs):
            b, l = xs
            def lf(p):
                return model.loss(p, None, b, l, rng=rng, train=True)[0]
            loss, g = jax.value_and_grad(lf)(s.params)
            # consume grads without optimizer state IO
            gsum = sum(jnp.sum(x.astype(jnp.float32)) for x in
                       jax.tree.leaves(g))
            return s, loss + 0.0 * gsum
        return jax.lax.scan(body, state, (batches, labels))[1]

    sec = median_dispatch(grad_multi, state0, batches, labels,
                          jax.random.key(1))
    emit("fwd_bwd_no_opt_scan16", sec / 16)

    # 7. XLA's own cost model for one full step
    one = gspmd.make_gspmd_train_step(model, mesh, tx)
    b1 = jax.tree.map(lambda x: x[0], make_inputs(1)[0])
    l1 = make_inputs(1)[1][0]
    ca = one.lower(state0, b1, l1, jax.random.key(1)).compile() \
            .cost_analysis()
    print(json.dumps({"cost_analysis": {
        "flops": ca.get("flops"),
        "bytes_accessed": ca.get("bytes accessed"),
        "opt_seconds": ca.get("optimal_seconds"),
    }}), flush=True)


if __name__ == "__main__":
    main()
