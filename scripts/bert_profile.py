#!/usr/bin/env python
"""Capture a device trace of a train step and print the op-level time
breakdown (xprof framework_op_stats), grouped by op category.

Answers "where do the milliseconds go" directly — the diagnosis
scripts/bert_diagnose.py locates the stall by ablation; this names it.
``--model bert_base`` (default) profiles the flagship MLM step;
``--model resnet50`` profiles the image step at its best-known config
(b128 + remat).
"""

from __future__ import annotations

import dataclasses as dc
import glob
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mpi_tensorflow_tpu.data import synthetic
from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.parallel import mesh as meshlib
from mpi_tensorflow_tpu.train import gspmd

B, S, K = 64, 128, 8


def build_bert(mesh):
    dropout = float(os.environ.get("PROF_DROPOUT", "0.1"))
    # default OFF: the shipping flagship is XLA dense attention (round-3
    # measurements, flash_min_seq=4096) — profile the step we are pushing,
    # not the retired kernel variant; PROF_FLASH=1 opts into the contrast
    use_flash = os.environ.get("PROF_FLASH", "0") == "1"
    # flash_min_seq=0 keeps PROF_FLASH meaningful at S=128 (the default
    # threshold would force XLA attention regardless — see bert_diagnose)
    cfg = dc.replace(bert.BERT_BASE, dtype=jnp.bfloat16, dropout=dropout,
                     flash_min_seq=0)
    model = bert.BertMlm(cfg, mesh=mesh, use_flash=use_flash)
    tx = optax.adamw(1e-4)
    state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh)
    multi = gspmd.make_gspmd_multi_step(model, mesh, tx)
    toks, tgts, mask = synthetic.mlm_batches(K * B, seq_len=S,
                                             vocab_size=30522, seed=0)
    shape = (K, B, S)
    batches = {"tokens": jnp.asarray(toks.reshape(shape)),
               "mask": jnp.asarray(mask.reshape(shape))}
    labels = jnp.asarray(tgts.reshape(shape))
    return multi, state, batches, labels


def build_resnet50(mesh):
    from mpi_tensorflow_tpu.config import Config
    from mpi_tensorflow_tpu.train import loop, step as step_lib

    b = int(os.environ.get("PROF_BATCH", "128"))
    cfg = Config(batch_size=b, precision="bf16", model="resnet50",
                 num_classes=1000, image_size=224,
                 remat=os.environ.get("PROF_REMAT", "1") == "1")
    model = loop.build_model(cfg)
    state = step_lib.init_state(model, jax.random.key(cfg.seed))
    multi = step_lib.make_multi_train_step(model, cfg, mesh,
                                           decay_steps=50000)
    rng = np.random.default_rng(0)
    kk = max(2, K // 4)   # 224^2 inputs: keep the staged bank in HBM
    batches = jnp.asarray(rng.normal(size=(kk, b, 224, 224, 3))
                          .astype(np.float32) * 0.3)
    labels = jnp.asarray(rng.integers(0, 1000, size=(kk, b))
                         .astype(np.int64))
    return multi, state, batches, labels


def main():
    global K
    model_name = "bert_base"
    if "--model" in sys.argv:
        model_name = sys.argv[sys.argv.index("--model") + 1]
    # stage prints flush immediately: on a timeout the queue's run_script
    # records the partial stdout, so the log names the stage that hung
    print(json.dumps({"stage": "client_init"}), flush=True)
    mesh = meshlib.make_mesh()
    print(json.dumps({"stage": "build", "model": model_name}), flush=True)
    if model_name == "resnet50":
        multi, state, batches, labels = build_resnet50(mesh)
        K = batches.shape[0]
    else:
        multi, state, batches, labels = build_bert(mesh)

    # warmup/compile
    print(json.dumps({"stage": "compile"}), flush=True)
    st, m = multi(state, batches, labels, jax.random.key(1))
    float(m["loss"][-1])
    print(json.dumps({"stage": "trace"}), flush=True)

    logdir = tempfile.mkdtemp(prefix="bertprof_")
    jax.profiler.start_trace(logdir)
    st, m = multi(st, batches, labels, jax.random.key(1))
    float(m["loss"][-1])
    jax.profiler.stop_trace()
    print(json.dumps({"stage": "convert"}), flush=True)

    xplanes = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    if not xplanes:
        print(json.dumps({"error": "no xplane captured", "dir": logdir}))
        return 1
    from collections import defaultdict

    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data(xplanes, "framework_op_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    out = os.environ.get("PROF_JSON", "/tmp/bert_op_stats.json")
    with open(out, "w") as f:
        f.write(data)
    # gviz-JSON: a list of table objects {cols: [{id,...}], rows: [{c:
    # [{v}, ...]}]} — typically [combined/device table, host table]
    tables = json.loads(data)
    if isinstance(tables, dict):
        tables = [tables]
    rows = []
    for tbl in tables:
        ids = [c["id"] for c in tbl.get("cols", [])]
        for r0 in tbl.get("rows", []):
            vals = [cell.get("v") if isinstance(cell, dict) else cell
                    for cell in r0.get("c", [])]
            rows.append(dict(zip(ids, vals)))
    dev = [r0 for r0 in rows
           if str(r0.get("host_or_device", "")).lower() == "device"]
    by_cat = defaultdict(float)
    total = 0.0
    def self_us(r0):
        # observed artifact exports 'total_self_time'; other xprof builds
        # use 'total_self_time_in_us' — accept either
        return float(r0.get("total_self_time",
                            r0.get("total_self_time_in_us")) or 0)

    for r0 in dev:
        t = self_us(r0)
        by_cat[str(r0.get("type", "?"))] += t
        total += t
    print(json.dumps({"json": out, "trace_dir": logdir,
                      "n_device_rows": len(dev)}))
    for cat, t in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print(f"{t/1e3/K:9.3f} ms/step  {100*t/max(total,1e-9):5.1f}%  {cat}")
    print(f"{total/1e3/K:9.3f} ms/step  device total (K={K} steps)")
    dev.sort(key=lambda r0: -self_us(r0))
    print("\ntop 25 device ops by self time "
          "(ms/step | %dev | bound_by | op):")
    for r0 in dev[:25]:
        t = self_us(r0)
        print(f"{t/1e3/K:9.3f}  {float(r0.get('device_total_self_time_percent') or 0):5.1f}%"
              f"  {str(r0.get('bound_by', '?')):10s}"
              f"  {str(r0.get('type', '?'))}: "
              f"{str(r0.get('operation', '?'))[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
