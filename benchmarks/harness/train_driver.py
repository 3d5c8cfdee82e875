"""Drives a training cell: the body of ``train/mlm_loop.train_mlm``'s loop,
in its order — slice the host batch, ``gspmd.shard_batch``, the step from
``gspmd.make_gspmd_train_step`` on a state from ``init_gspmd_state`` with
``optimizer.transformer_tx`` — with no per-step host sync.

Set-up builds ONE compiled step with its state, drives it through the
first ``check_steps`` steps (which also warm it up) and hands the same
objects to the window.  Once the window has closed and the state is freed,
the plain reference follows those first steps from the same weights,
batches and dropout key, and ``check`` compares.

Mesh, parameter sharding, precision, batch and sequence length are data
(``configs/<config>.json``, ``traffic/<mix>.json``).
"""

from __future__ import annotations

import time

import numpy as np

from . import check, flops, traffic
from ..reference import bert_mlm as ref_mlm
from ..reference import transformer as ref_tf


def _find_adam(opt_state):
    """The optimizer state's Adam moments (the node with ``mu``)."""
    import jax

    nodes = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(nodes) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(nodes)}")
    return nodes[0]


class _SeededModel:
    """The program's model with ``init`` returning the benchmark's
    weights, so ``init_gspmd_state`` places those."""

    def __init__(self, model, params):
        self._model = model
        self._params = params

    def init(self, rng):
        return self._params

    def __getattr__(self, name):
        return getattr(self._model, name)


class TrainCell:
    """The compiled step, its feed and its state for one cell and seed."""

    def __init__(self, cell: dict, devices, seed: int, phase=None):
        import jax
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.models import bert
        from mpi_tensorflow_tpu.parallel import mesh as meshlib
        from mpi_tensorflow_tpu.train import gspmd
        from mpi_tensorflow_tpu.train import optimizer as opt_lib

        cfg, mix = cell["config_data"], cell["traffic_data"]
        prog = cfg["program"]
        if prog["model"] != "bert_mlm":
            raise ValueError(f"train driver has no model {prog['model']!r}")
        if mix.get("param_sharding", "replicated") != "replicated":
            raise ValueError("train driver: only replicated parameters")
        self.sz = ref_tf.sizes(cfg)
        self.prog, self.mix, self.seed = prog, mix, int(seed)
        chips = len(devices)
        shape = {k: (chips if v == "chips" else int(v))
                 for k, v in mix["mesh"].items()}
        self.mesh = meshlib.make_mesh(shape, devices=devices)
        self.batch = int(mix["per_chip_batch"]) * self.mesh.shape["data"]
        self.seq_len = int(mix["seq_len"])
        self.rate = float(prog["dropout"])
        bcfg = bert.BertConfig(
            vocab_size=self.sz["vocab"], hidden=self.sz["hidden"],
            layers=self.sz["layers"], heads=self.sz["heads"],
            mlp=self.sz["mlp"], max_positions=self.sz["positions"],
            dropout=self.rate, dtype=jnp.dtype(prog["compute_dtype"]))
        self.model = bert.BertMlm(bcfg, mesh=self.mesh)
        self.head_positions = bert.ce_capacity(bcfg, self.seq_len)
        if self.head_positions != ref_mlm.capacity(self.seq_len):
            raise RuntimeError("program and reference disagree on the "
                               "masked-position capacity")
        self.tx = opt_lib.transformer_tx(
            float(prog["learning_rate"]), int(prog["schedule_steps"]),
            schedule=prog["schedule"], optimizer=prog["optimizer"])
        self.step_fn = gspmd.make_gspmd_train_step(
            self.model, self.mesh, self.tx)
        self._gspmd = gspmd
        self._make_params = jax.jit(
            lambda key: ref_tf.init_params(self.sz, key))
        self._phase = phase or (lambda name: None)
        self._phase("model, optimizer, step function")
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Weights, state, data and dropout key of ``seed``; the compiled
        step stays."""
        import jax

        self.seed = int(seed)
        mix = self.mix
        params = self._make_params(jax.random.key(self.seed))
        check.require_weight_tree(self.model, params)
        self._phase("weights from the seed")
        self.state = self._gspmd.init_gspmd_state(
            _SeededModel(self.model, params), self.tx,
            jax.random.key(self.seed), self.mesh)
        self._phase("state placed, optimizer state")
        n = int(mix["epoch_batches"]) * self.batch
        self.data = traffic.mlm_batches(
            n, seq_len=self.seq_len, vocab_size=self.sz["vocab"],
            mask_token=int(mix["mask_token"]),
            mask_rate=float(mix["mask_rate"]), seed=self.seed)
        self.n_rows = n
        # the training rng stream, as Config.make_train_key(seed + 2)
        self.rng = jax.random.key(self.seed + 2, impl="threefry2x32")
        self.t = 0
        self._phase("batches from the seed")

    def rows_of(self, t: int) -> slice:
        lo = (t * self.batch) % max(self.n_rows - self.batch, 1)
        return slice(lo, lo + self.batch)

    def step(self, spans):
        """One pass of the loop body; returns the step's metrics (device
        arrays, not waited for)."""
        d, r = self.data, self.rows_of(self.t)
        with spans.span("train_host_feed"):
            batch = self._gspmd.shard_batch(
                {"tokens": d["tokens"][r], "mask": d["mask"][r]}, self.mesh)
            tgt = self._gspmd.shard_batch(d["targets"][r], self.mesh)
            self.state, metrics = self.step_fn(self.state, batch, tgt,
                                               self.rng)
        self.t += 1
        return metrics

    def first_steps(self, spans, n: int) -> dict:
        """Drive the first ``n`` steps through ``step`` and read what the
        check compares (see ``check.training_numbers``)."""
        import jax
        import jax.numpy as jnp

        start = jax.tree.map(jnp.copy, self.state.params)
        losses, grad = [], None
        for i in range(n):
            losses.append(self.step(spans)["loss"])
            if i == 0:
                # Adam's first moment after one step is (1 - b1) x the
                # gradient the optimizer got
                grad = ref_mlm.leaf_norms(_find_adam(self.state.opt).mu)
        change = ref_mlm.diff_norms(self.state.params, start)
        jax.block_until_ready(self.state)
        return {"losses": np.asarray([float(x) for x in losses]),
                "grad_norms": np.asarray(grad) / (1.0 - ref_mlm.B1),
                "change_norms": np.asarray(change)}

    def reference_steps(self, n: int, precision: str = "f32",
                        fault=None) -> dict:
        """The plain reference over the same first ``n`` steps.  Call it
        with the program's state freed."""
        import jax

        params = self._make_params(jax.random.key(self.seed))
        batches = [{k: v[self.rows_of(t)] for k, v in self.data.items()}
                   for t in range(n)]
        return ref_mlm.run_steps(
            params, batches, self.rng, rate=self.rate,
            base_lr=float(self.prog["learning_rate"]),
            schedule_steps=int(self.prog["schedule_steps"]),
            precision=precision, fault=fault,
            block_rows=int(self.mix["reference_block_rows"]))

    def free(self) -> None:
        self.state = None


def run(cell: dict, devices, args, clock) -> dict:
    """One run of a training cell.  ``clock`` has ``t_start`` (process
    start on ``time.perf_counter``), ``spans``, ``compiles`` and the
    ``tracer`` context factory."""
    import jax

    spans = clock.spans
    tc = TrainCell(cell, devices, args.seed, phase=clock.phase)
    mix = tc.mix
    n_check = int(mix["check_steps"])
    prog_numbers = tc.first_steps(spans, n_check)
    clock.phase("first steps (compile or cache load, warm-up)")
    setup_s = time.perf_counter() - clock.t_start

    sync_every = int(mix["sync_every"])
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(mix["trace_seconds"]))
    c0 = clock.compiles.mark()[0]
    steps = 0
    with clock.tracer():
        t0 = time.perf_counter()
        with spans.span("window"):
            while True:
                tc.step(spans)
                steps += 1
                if steps % sync_every == 0:
                    with spans.span("train_sync"):
                        jax.block_until_ready(tc.state)
                    if time.perf_counter() - t0 >= seconds:
                        break
        window_s = time.perf_counter() - t0
    compiles = clock.compiles.mark()[0] - c0
    device = clock.describe(devices)
    tc.free()

    clock.phase("window closed, state freed")
    ref_numbers = tc.reference_steps(n_check)
    clock.phase("reference followed")
    numbers = check.training_numbers(prog_numbers, ref_numbers)
    tokens = steps * tc.batch * tc.seq_len
    return {
        "attempted": steps, "failed": 0, "numbers": numbers,
        "device": device, "window": (t0, t0 + window_s),
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": setup_s},
        "work": {"steps": steps, "tokens": tokens, "window_s": window_s,
                 "compiles_in_window": compiles, "chips": len(devices),
                 "flops": steps * flops.train_step_flops(
                     tc.sz, tc.batch, tc.seq_len, tc.head_positions)},
    }
