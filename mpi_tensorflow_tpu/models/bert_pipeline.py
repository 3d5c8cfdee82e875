"""Pipeline-parallel BERT-MLM: the encoder stack as GPipe stages.

``PipelinedBertMlm`` is the real-model counterpart of the generic schedule
in parallel/pipeline.py (which round 1 only exercised with toy stage fns):
the L encoder layers are split into ``pipe`` stages of L/P layers whose
parameters carry a leading ``stage`` logical axis sharded over the ``pipe``
mesh axis.  Embeddings and the MLM head stay replicated outside the
pipeline (they are ~1% of encoder FLOPs at BERT-base geometry).  The full
*training* step — loss, backward, optimizer — runs through the schedule:
``train/gspmd.make_gspmd_train_step`` works unchanged because this is just
a ``BertMlm`` whose encoder calls ``parallel.pipeline.pipeline`` inside a
``shard_map``; reverse-mode autodiff of the scanned schedule yields the
backward pipeline (reverse ``ppermute`` hops) automatically.

Composition: ``pipe x model x data`` — each data shard runs its own
microbatch stream through the stages, and when the mesh has a ``model``
axis the per-stage compute is Megatron tensor-parallel (heads/MLP-hidden
column-parallel in, manual row-parallel psums; ``_plain_layer`` tp_axis).  The loss-side machinery (masked-position
packing, chunked CE) is inherited.  Dropout trains unmodified: the
schedule hands each stage the index of the microbatch it is processing
(parallel/pipeline.py ``with_mb_index``), and dropout keys are folded on
(data shard, microbatch, global layer, site) so every microbatch draws
independent masks — including under remat, which replays the same fold
inputs and hence identical masks in the recomputation.

Memory schedules, from cheapest to most capable:
- GPipe (``schedule="gpipe"``, default): the scanned forward pipeline with
  autodiff backward — stores ~M microbatch boundary activations; bubble
  (P-1)/(M+P-1) each way.
- Microbatch groups: ``num_microbatches = P`` + the train step's
  ``grad_accum`` — O(P) activations at bubble (P-1)/(2P-1) per group
  (pinned by tests/test_pipeline_bert.py::test_pipeline_with_grad_accum).
- Interleaved 1F1B (``schedule="1f1b"``): hand-interleaved
  one-forward-one-backward via ``parallel/pipeline.pipeline_1f1b`` — the
  same (P-1)/(M+P-1) bubble as end-to-end GPipe but only O(P) stashed
  activations (each stage's backward recomputes its forward from the
  stashed input).  Loss/grad parity with GPipe is pinned by
  tests/test_pipeline_1f1b.py::TestOneFOneB.
``cfg.remat`` additionally recomputes within-stage activations in the
backward.  TP inside a stage works with both schedules (the 1F1B path
runs a vocab-parallel CE in-schedule); SP inside a stage works with
BOTH schedules too — activations sequence-sharded over the ``seq`` mesh
axis, stage attention as blockwise ring attention (ppermute neighbor
hops), dropout decorrelated per (data, seq) shard — composing to
``pipe x model x seq x data``.  Under 1F1B the in-schedule CE must be
position-local (``ce_positions="all"``; guarded — masked-position
packing gathers across the sequence), and the schedule runs its stage
bodies unconditionally every tick (collectives inside a slot-gated
``lax.cond`` are unsound — see ``pipeline.pipeline_1f1b``).

No counterpart in the reference (SURVEY.md §2 checklist: PP absent).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mpi_tensorflow_tpu.models import bert as bert_lib
from mpi_tensorflow_tpu.models.bert import _layernorm
from mpi_tensorflow_tpu.parallel import pipeline as pipeline_lib
from mpi_tensorflow_tpu.parallel import ring


def _float0(x):
    """Zero cotangent for a non-differentiable input (ints, prng keys)."""
    import numpy as np

    return np.zeros(jnp.shape(x), jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sched_loss(run, sp, hp, h, labels, mask, inv, key):
    """Splice the 1F1B schedule's manually accumulated gradients into the
    outer autodiff: the schedule computes loss AND grads in one interleaved
    pass (that is its point), so the VJP just scales the saved grads by the
    upstream cotangent.  ``run`` is the shard_mapped schedule (static)."""
    return run(sp, hp, h, labels, mask, inv, key)[0]


def _sched_fwd(run, sp, hp, h, labels, mask, inv, key):
    loss, gs, gl, dmb = run(sp, hp, h, labels, mask, inv, key)
    return loss, (gs, gl, dmb.astype(h.dtype), labels, mask, inv, key)


def _sched_bwd(run, res, ct):
    gs, gl, dmb, labels, mask, inv, key = res
    scale = lambda tree: jax.tree.map(lambda x: x * ct, tree)  # noqa: E731
    return (scale(gs), scale(gl), (dmb * ct).astype(dmb.dtype),
            _float0(labels), _float0(mask),
            jnp.zeros_like(inv), _float0(key))


_sched_loss.defvjp(_sched_fwd, _sched_bwd)


def stack_layers(layers: list, num_stages: int):
    """List of L per-layer param dicts -> stacked pytree of
    (num_stages, L/num_stages, ...) arrays (stage-major, layer order
    preserved)."""
    L = len(layers)
    if L % num_stages:
        raise ValueError(f"{L} layers not divisible by {num_stages} stages")
    return jax.tree.map(
        lambda *xs: jnp.stack(xs).reshape(
            (num_stages, L // num_stages) + xs[0].shape), *layers)


def stack_layers_interleaved(layers: list, num_stages: int, v: int):
    """Interleaved chunk stacking: (P, v, L/(vP), ...) where
    ``stacked[d, j]`` holds GLOBAL chunk ``k = j * P + d`` (layers
    ``k*Lc .. (k+1)*Lc``) — chunks ascend round-robin over devices so
    every pipeline hop is the +1 ring neighbor
    (parallel/pipeline.pipeline_1f1b_interleaved)."""
    L, P_ = len(layers), num_stages
    V = v * P_
    if L % V:
        raise ValueError(f"{L} layers not divisible by {V} chunks "
                         f"({P_} stages x {v} virtual)")
    Lc = L // V

    def stack(*xs):
        flat = jnp.stack(xs)                       # (L, ...)
        ch = flat.reshape((V, Lc) + xs[0].shape)   # chunk-major
        # [k] -> [d, j] with k = j*P + d
        return jnp.moveaxis(ch.reshape((v, P_, Lc) + xs[0].shape), 0, 1)

    return jax.tree.map(stack, *layers)


def unstack_interleaved(stacked, num_stages: int, v: int):
    """Inverse layout map: (P, v, Lc, ...) -> GPipe's (P, v*Lc, ...)
    stage-major order (stage s = chunks s*v .. s*v+v-1 = sequential
    layers).  Pure jnp reshuffle — at the GSPMD level the compiler
    inserts the pipe-axis data movement; used for the forward-only
    (eval/encode) paths, which keep the GPipe scan."""
    P_ = num_stages

    def un(x):
        Lc = x.shape[2]
        ch = jnp.moveaxis(x, 0, 1).reshape((v * P_ * Lc,) + x.shape[3:])
        return ch.reshape((P_, v * Lc) + x.shape[3:])

    return jax.tree.map(un, stacked)


@dataclasses.dataclass(frozen=True)
class PipelinedBertMlm(bert_lib.BertMlm):
    """BERT-MLM with the encoder pipelined over the mesh's ``pipe`` axis.

    ``schedule``: "gpipe" (the scanned forward pipeline; backward derived
    by autodiff — stores M microbatch boundary activations) or "1f1b"
    (interleaved one-forward-one-backward, parallel/pipeline.py
    ``pipeline_1f1b`` — same (P-1)/(M+P-1) bubble, but only O(P) stashed
    activations, the pod-scale memory schedule).  "1f1b" applies to the
    training loss; forward-only encode/apply always use the GPipe scan
    (there is no backward to interleave with)."""
    num_microbatches: int = 4
    schedule: str = "gpipe"
    virtual_stages: int = 1     # v chunks/device for "1f1b_interleaved"

    @property
    def _num_stages(self) -> int:
        return self.mesh.shape.get("pipe", 1) if self.mesh is not None else 1

    @property
    def _interleaved(self) -> bool:
        return self.schedule == "1f1b_interleaved" and self.virtual_stages > 1

    def __post_init__(self):
        if self.schedule not in ("gpipe", "1f1b", "1f1b_interleaved"):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.schedule == "1f1b_interleaved" and self.virtual_stages < 1:
            raise ValueError("virtual_stages must be >= 1")
        if self._interleaved and self.mesh is not None:
            V = self._num_stages * self.virtual_stages
            if self.cfg.layers % max(V, 1):
                raise ValueError(
                    f"{self.cfg.layers} layers not divisible by "
                    f"{V} chunks ({self._num_stages} stages x "
                    f"{self.virtual_stages} virtual)")
        if self.cfg.pos_kind != "learned":
            # the pipelined stage fn replicates the plain layer math
            # WITHOUT the rope rotation; guarding at CONSTRUCTION covers
            # every entry point (incl. checkpoint restore that skips
            # init()) — failing loudly beats training a silently
            # position-blind model
            raise ValueError(
                f"pipelined BERT supports pos_kind='learned' only "
                f"(got {self.cfg.pos_kind!r})")
        if self.schedule in ("1f1b", "1f1b_interleaved") \
                and self.mesh is not None \
                and self.mesh.shape.get("seq", 1) > 1 \
                and self.cfg.ce_positions != "all":
            # the 1F1B path computes the head/CE INSIDE the schedule:
            # with ce_positions="all" that math is position-local (the
            # tied decoder + CE act per position) and composes with
            # sequence sharding via local sums + a seq psum — but the
            # "masked" packing gathers rows ACROSS the sequence and is
            # not sequence-parallel; fail rather than silently unpack
            raise ValueError(
                "schedule='1f1b' under a 'seq' mesh axis needs "
                "ce_positions='all' (masked-position packing gathers "
                "across the sequence and is not sequence-parallel); "
                "use ce_positions='all' or the gpipe schedule")

    def init(self, rng):
        params = super().init(rng)
        if self._interleaved:
            params["layers"] = stack_layers_interleaved(
                params["layers"], self._num_stages, self.virtual_stages)
        else:
            params["layers"] = stack_layers(params["layers"],
                                            self._num_stages)
        return params

    def logical_axes(self):
        axes = super().logical_axes()
        layer0 = axes["layers"][0]
        lead = ("stage", "vchunk", "layer") if self._interleaved \
            else ("stage", "layer")
        axes["layers"] = {k: lead + v
                          for k, v in layer0.items()
                          if not isinstance(v, dict)}
        for k, v in layer0.items():
            if isinstance(v, dict):   # layernorm sub-dicts
                axes["layers"][k] = {kk: lead + vv
                                     for kk, vv in v.items()}
        return axes

    def _plain_layer(self, lp, h, drop=None, tp_axis=None, seq_axis=None):
        """One encoder layer with no mesh constraints — runs inside the
        pipe ``shard_map`` where GSPMD annotations are unavailable.  Same
        math as BertMlm's layer.  ``drop``: ``None`` (eval / dropout off) or
        a ``site -> key`` function yielding this layer's per-site dropout
        keys (already folded on microbatch and global layer index).

        ``tp_axis``: Megatron tensor parallelism INSIDE the stage — the
        stage's heads/MLP-hidden arrive sharded over that mesh axis
        (column-parallel in), and the two row-parallel output projections
        are manually ``psum``'d; biases of the row-parallel outputs are
        added once, after the reduction.

        ``seq_axis``: sequence parallelism INSIDE the stage — ``h``
        arrives sequence-sharded over that mesh axis and attention runs
        as blockwise ring attention (``parallel/ring.ring_attention``,
        ppermute neighbor hops); everything else in the layer is
        position-local and needs no change.  Composes with ``tp_axis``
        (attention is independent per local head subset)."""
        dt = self.cfg.dtype

        def dropout(x, site):
            if drop is None:
                return x
            return bert_lib.dropout_mask(x, self.cfg.dropout, drop(site))

        reduce = None if tp_axis is None else \
            (lambda x: lax.psum(x, tp_axis))
        q, k, v = bert_lib.qkv_proj(lp, h, dt,   # local head subset if TP
                                    fused=self.cfg.fused_qkv)
        # self.causal: False for the MLM family, True for the pipelined
        # causal LM (models/gpt.PipelinedCausalLm) — the mask is the only
        # attention difference, exactly as on the non-pipelined path
        if seq_axis is not None:
            a = ring.ring_attention(q, k, v, seq_axis, causal=self.causal)
        else:
            a = ring.dense_attention(q, k, v, causal=self.causal)
        a = bert_lib.attn_out_proj(lp, a, dt, reduce=reduce)
        h = _layernorm(h + dropout(a, 0), lp["ln1"]).astype(dt)
        m = self._plain_mlp(lp, h, reduce)
        return _layernorm(h + dropout(m, 1), lp["ln2"]).astype(dt)

    def _plain_mlp(self, lp, h, reduce):
        """Stage-interior MLP hook — dense GELU here; the pipelined MoE
        variant (models/moe.PipelinedMoeBertMlm) swaps in the routed
        expert dispatch."""
        return bert_lib.gelu_mlp(lp, h, self.cfg.dtype, reduce=reduce)

    def _dropping(self, train: bool, rng) -> bool:
        if not (train and self.cfg.dropout > 0.0):
            return False
        if rng is None:
            raise ValueError("dropout needs an rng in train mode")
        return True

    def _stage(self, stage_params, x, rng=None, mb_idx=None,
               stage_idx=None, tp_axis=None, seq_axis=None):
        """Run this stage's L/P layers sequentially (scan over the layer
        dim of the stacked params).  When ``rng`` is set, dropout keys are
        folded on (microbatch, global layer, site) so every microbatch at
        every layer draws an independent mask — and a remat recomputation
        replays the identical mask (keys are pure functions of the fold
        inputs)."""
        Lp = jax.tree.leaves(stage_params)[0].shape[0]

        def body(h, inp):
            lp, li = inp
            drop = None
            if rng is not None:
                gl = stage_idx * Lp + li      # global layer index
                kb = jax.random.fold_in(jax.random.fold_in(rng, mb_idx), gl)
                drop = lambda site: jax.random.fold_in(kb, site)  # noqa: E731
            return self._plain_layer(lp, h, drop=drop, tp_axis=tp_axis,
                                     seq_axis=seq_axis), None

        if self.cfg.remat:
            # recompute stage activations in the backward pipeline: the
            # scanned schedule then stores only stage-boundary activations
            # per tick instead of every layer's internals (the GPipe
            # activation-memory story).  The remat_policy mapping is the
            # shared one (bert.remat_policy_fn) — "dots" keeps matmul
            # outputs here exactly as on the non-pipelined path
            body = jax.checkpoint(
                body, policy=bert_lib.remat_policy_fn(self.cfg))
        h, _ = lax.scan(body, x, (stage_params, jnp.arange(Lp)))
        return h

    def _embed(self, params, tokens, dropping: bool, rng):
        """Token+position embeddings (+LN, + the first dropout site) — the
        replicated front section shared by both pipeline schedules."""
        c = self.cfg
        S = tokens.shape[1]
        h = params["tok_emb"][tokens] + params["pos_emb"][None, :S]
        h = _layernorm(h, params["emb_ln"])
        if dropping:
            # embedding dropout (BertMlm's first site), on a stream index
            # no in-stage fold chain can collide with
            h = bert_lib.dropout_mask(h, c.dropout,
                                      jax.random.fold_in(rng, 2 ** 30))
        h = h.astype(c.dtype)
        return self._constrain(h, ("batch", "seq", "embed"))

    def _encode_aux(self, params, tokens, *, train: bool = False, rng=None):
        if self._interleaved:
            # forward-only paths keep the GPipe scan: fold the (P, v, Lc)
            # chunk layout back to stage-major (P, v*Lc) — a pure jnp
            # reshuffle whose pipe-axis data movement GSPMD inserts
            params = dict(params, layers=unstack_interleaved(
                params["layers"], self._num_stages, self.virtual_stages))
        dropping = self._dropping(train, rng)
        B, S = tokens.shape
        h = self._embed(params, tokens, dropping, rng)

        n_stages = self._num_stages
        if n_stages == 1:   # no pipe axis: plain sequential stack
            flat = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), params["layers"])
            h = self._stage(flat, h, rng=rng if dropping else None,
                            mb_idx=jnp.int32(0), stage_idx=jnp.int32(0))
            return h, jnp.zeros((), jnp.float32)

        M = self.num_microbatches
        dp = self.mesh.shape.get("data", 1)
        sp = self.mesh.shape.get("seq", 1)
        if (B // dp) % M:
            raise ValueError(
                f"per-data-shard batch {B // dp} not divisible by "
                f"{M} microbatches")
        if S % sp:
            raise ValueError(
                f"sequence length {S} not divisible by the seq axis {sp}")
        h_spec = P("data" if dp > 1 else None, "seq" if sp > 1 else None)
        tp_axis = "model" if self.mesh.shape.get("model", 1) > 1 else None
        seq_axis = "seq" if sp > 1 else None

        def inner(stacked_local, hl, key):
            stage_params = jax.tree.map(lambda x: x[0], stacked_local)
            mb = hl.reshape((M, hl.shape[0] // M) + hl.shape[1:])
            if dropping:
                # decorrelate the data AND seq shards' masks (each holds
                # a different slice of the global (B, S) activation
                # grid); model shards keep the SAME key — their outputs
                # are replicated.  sp==1 reduces to the data-only fold.
                shard_id = (lax.axis_index("data") if dp > 1 else 0) * sp \
                    + (lax.axis_index("seq") if sp > 1 else 0)
                key = jax.random.fold_in(key, shard_id)
                sidx = lax.axis_index("pipe")
                out = pipeline_lib.pipeline(
                    lambda p, x, mi: self._stage(p, x, rng=key, mb_idx=mi,
                                                 stage_idx=sidx,
                                                 tp_axis=tp_axis,
                                                 seq_axis=seq_axis),
                    stage_params, mb, "pipe", with_mb_index=True)
            else:
                out = pipeline_lib.pipeline(
                    lambda p, x: self._stage(p, x, tp_axis=tp_axis,
                                             seq_axis=seq_axis),
                    stage_params, mb, "pipe")
            return out.reshape(hl.shape)

        key = rng if dropping else jax.random.key(0)
        h = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(self._stage_param_specs(gpipe_layout=True), h_spec,
                      P()),
            out_specs=h_spec,
            check_vma=False)(params["layers"], h, key)
        h = self._constrain(h, ("batch", "seq", "embed"))
        return h, jnp.zeros((), jnp.float32)

    def _stage_param_specs(self, gpipe_layout: bool = False):
        """Per-leaf shard_map in_specs for the stacked stage params: the
        rule-table layout (stage -> pipe, heads/mlp -> model when the mesh
        has a model axis) — the specs must tell shard_map the truth about
        how ``shard_tree``/GSPMD placed the parameters, or TP-inside-stage
        would silently gather.

        ``gpipe_layout``: specs for the stage-major (P, v*Lc, ...) view
        ``unstack_interleaved`` produces (the vchunk dim folded away);
        no-op unless the model is interleaved."""
        from mpi_tensorflow_tpu.parallel import sharding_rules

        axes = self.logical_axes()["layers"]
        if gpipe_layout and self._interleaved:
            strip = lambda t: tuple(a for a in t if a != "vchunk")
            axes = jax.tree.map(
                strip, axes, is_leaf=lambda x: isinstance(x, tuple))
        return sharding_rules.tree_specs(axes, self.mesh, self.rules)

    # ------------------------------------------------------------------
    # interleaved 1F1B training path
    # ------------------------------------------------------------------

    def _mb_loss(self, head_params, y, labels_i, mask_i, inv,
                 tp_axis=None):
        """Microbatch loss contribution (already globally normalized by
        ``inv`` = 1/total masked count, so contributions SUM to the same
        loss the GPipe path computes).  Runs on the last stage only.

        ``tp_axis``: the vocab decoder (``tok_emb``/``out_b``) arrives
        vocab-sharded over that axis — CE then goes through the sharded
        logsumexp in ``_vocab_parallel_ce``."""
        c = self.cfg
        if c.ce_positions == "masked":
            from mpi_tensorflow_tpu.ops import mlm_head

            bert_lib.engagement.record("ce_positions", "masked_packed")
            packed, plab, w = mlm_head.gather_masked_rows(
                y, labels_i, mask_i.astype(jnp.bool_),
                bert_lib.ce_capacity(c, y.shape[1]))
            t = self.head_hidden(head_params, packed)
            ce = self._vocab_parallel_ce(head_params, t, plab, tp_axis) \
                if tp_axis is not None else self._ce(head_params, t, plab)
            weights = w
        else:
            bert_lib.engagement.record("ce_positions", "all")
            t = self.head_hidden(head_params, y)
            ce = self._vocab_parallel_ce(head_params, t, labels_i, tp_axis) \
                if tp_axis is not None \
                else self._ce(head_params, t, labels_i)
            weights = mask_i.astype(jnp.float32)
        return jnp.sum(ce * weights) * inv

    def _vocab_parallel_ce(self, head_params, t, labels, tp_axis):
        """Tied-decoder CE with the vocab axis sharded over ``tp_axis``
        (manual collectives — runs inside the 1F1B shard_map where GSPMD
        is unavailable).  Each shard scores its local vocab slice; the
        softmax statistics and the gold logit are reduced across shards:
        logz = log(psum(sum(exp(l - pmax)))) + pmax, and the gold logit is
        psum of the one shard that owns the label's row."""
        dt = self.cfg.dtype
        logits = jnp.einsum("bse,ve->bsv", t,
                            head_params["tok_emb"].astype(dt)) \
            + head_params["mlm"]["out_b"]
        logits = logits.astype(jnp.float32)
        v_loc = logits.shape[-1]
        lo = lax.axis_index(tp_axis) * v_loc
        # the max is numerical stabilization only (it cancels exactly in
        # logz's gradient) — detached; pmax has no differentiation rule,
        # so the cross-shard max goes through all_gather (which has one)
        m = lax.stop_gradient(jnp.max(
            lax.all_gather(jnp.max(logits, axis=-1), tp_axis, axis=0),
            axis=0))
        se = lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                      tp_axis)
        logz = jnp.log(se) + m
        in_range = (labels >= lo) & (labels < lo + v_loc)
        loc = jnp.clip(labels - lo, 0, v_loc - 1)
        gold_loc = jnp.take_along_axis(logits, loc[..., None], axis=-1)[..., 0]
        gold = lax.psum(jnp.where(in_range, gold_loc, 0.0), tp_axis)
        return logz - gold

    def loss(self, params, model_state, batch, labels, *, rng=None,
             train: bool = False):
        if self.schedule not in ("1f1b", "1f1b_interleaved") \
                or self._num_stages == 1 or not train:
            bert_lib.engagement.record("pp_schedule", "gpipe")
            return super().loss(params, model_state, batch, labels,
                                rng=rng, train=train)
        bert_lib.engagement.record(
            "pp_schedule",
            "1f1b_interleaved" if self._interleaved else "1f1b")

        c = self.cfg
        tokens, mask = batch["tokens"], batch["mask"]
        B, S = tokens.shape
        dropping = self._dropping(train, rng)
        M = self.num_microbatches
        dp = self.mesh.shape.get("data", 1)
        sp = self.mesh.shape.get("seq", 1)
        if (B // dp) % M:
            raise ValueError(
                f"per-data-shard batch {B // dp} not divisible by "
                f"{M} microbatches")
        if S % sp:
            raise ValueError(
                f"sequence length {S} not divisible by the seq axis {sp}")
        h = self._embed(params, tokens, dropping, rng)
        # global normalizer, fixed before the schedule (data-only, no
        # grad): per-microbatch SUMS scaled by it add up to exactly the
        # GPipe path's globally normalized mean — and, under sequence
        # sharding, per-(data, seq)-shard partial sums scaled by it add
        # up the same way (the "all" CE is position-local)
        inv = 1.0 / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        head_params = {"mlm": params["mlm"], "tok_emb": params["tok_emb"]}
        key = rng if dropping else jax.random.key(0)
        h_spec = P("data" if dp > 1 else None, "seq" if sp > 1 else None)
        tp_axis = "model" if self.mesh.shape.get("model", 1) > 1 else None
        seq_axis = "seq" if sp > 1 else None
        # the in-schedule head/CE math runs INSIDE shard_map, where GSPMD
        # sharding constraints are illegal — a mesh-free view of this model
        # computes the same math without annotations
        plain = dataclasses.replace(self, mesh=None)
        from mpi_tensorflow_tpu.parallel import sharding_rules

        axes = self.logical_axes()
        hp_specs = sharding_rules.tree_specs(
            {"mlm": axes["mlm"], "tok_emb": axes["tok_emb"]}, self.mesh,
            self.rules)
        sp_specs = self._stage_param_specs()

        def _reduce_partials(grads, specs):
            """Under manual vjp inside shard_map, a REPLICATED parameter's
            cotangent comes back as per-model-shard partials whose sum is
            the true grad; model-sharded leaves are already local-true.
            Sum exactly the leaves whose spec does not mention the axis."""
            if tp_axis is None:
                return grads
            return jax.tree.map(
                lambda g, spec: g if tp_axis in spec
                else lax.psum(g, tp_axis), grads, specs)

        def inner(stacked_local, hp, hl, labels_l, mask_l, inv, key):
            sp_params = jax.tree.map(lambda x: x[0], stacked_local)
            mbsz = hl.shape[0] // M
            mb = hl.reshape((M, mbsz) + hl.shape[1:])
            lab = labels_l.reshape((M, mbsz) + labels_l.shape[1:])
            msk = mask_l.reshape((M, mbsz) + mask_l.shape[1:])
            if dropping:
                # same (data, seq) shard fold as the GPipe path — the
                # cross-schedule mask-identity pin depends on it
                shard_id = (lax.axis_index("data") if dp > 1 else 0) \
                    * sp + (lax.axis_index("seq") if sp > 1 else 0)
                key = jax.random.fold_in(key, shard_id)
            sidx = lax.axis_index("pipe")

            def stage_fn(p, x, mi):
                return self._stage(p, x, rng=key if dropping else None,
                                   mb_idx=mi, stage_idx=sidx,
                                   tp_axis=tp_axis, seq_axis=seq_axis)

            def last_fn(hp, y, aux):
                # ce_positions="all" under seq sharding: the tied
                # decoder + CE act per position, so the local slice's
                # sum * inv is this shard's partial of the global mean
                labels_i, mask_i = aux
                return plain._mb_loss(hp, y, labels_i, mask_i, inv,
                                      tp_axis=tp_axis)

            # stage bodies carry collectives whenever TP or SP is inside
            # them — those meshes need uniform (unconditional) stage
            # execution; plain pipe x data keeps the slot-gated fast path
            uniform = tp_axis is not None or seq_axis is not None
            if self._interleaved:
                def chunk_fn(p, x, mi, kg):
                    # kg = GLOBAL chunk index: _stage derives the global
                    # layer as stage_idx * Lp + li, and the chunk's Lp is
                    # L/(vP) — masks match the gpipe/1f1b schedules
                    return self._stage(p, x,
                                       rng=key if dropping else None,
                                       mb_idx=mi, stage_idx=kg,
                                       tp_axis=tp_axis, seq_axis=seq_axis)

                loss, gs, gl, dmb = pipeline_lib.pipeline_1f1b_interleaved(
                    chunk_fn, last_fn, sp_params, hp, mb, (lab, msk),
                    "pipe", v=self.virtual_stages,
                    n_stages=self._num_stages, uniform_stages=uniform)
            else:
                loss, gs, gl, dmb = pipeline_lib.pipeline_1f1b(
                    stage_fn, last_fn, sp_params, hp, mb, (lab, msk),
                    "pipe", uniform_stages=uniform)
            gl = _reduce_partials(gl, hp_specs)
            gs = _reduce_partials(gs, sp_specs)
            if tp_axis is not None:
                dmb = lax.psum(dmb, tp_axis)   # h is model-replicated
                # the microbatch loss is computed REPLICATED across model
                # shards, so last_fn's vjp seeds the cotangent once per
                # shard — every accumulated gradient carries a factor of
                # tp; normalize once here (the loss VALUE is replicated,
                # not summed, and needs no correction)
                tp = self.mesh.shape["model"]
                gs, gl, dmb = jax.tree.map(lambda x: x / tp,
                                           (gs, gl, dmb))
            # sum loss/replicated-param grads over the data shards (each
            # saw a different batch slice of the global mean) AND the seq
            # shards (each saw a different position slice; params are
            # seq-replicated, so their cotangents are partials — dmb is
            # seq-SHARDED and already local-true)
            red = tuple(a for a, n in (("data", dp), ("seq", sp))
                        if n > 1)
            if red:
                loss = lax.psum(loss, red)
                gl = jax.tree.map(lambda x: lax.psum(x, red), gl)
                gs = jax.tree.map(lambda x: lax.psum(x, red), gs)
            # restore the stacked leading stage axis for the out_spec
            gs = jax.tree.map(lambda x: x[None], gs)
            return loss, gs, gl, dmb.reshape(hl.shape)

        run = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(sp_specs, hp_specs, h_spec, h_spec, h_spec,
                      P(), P()),
            out_specs=(P(), sp_specs, hp_specs, h_spec),
            check_vma=False)

        loss = _sched_loss(run, params["layers"], head_params, h, labels,
                           mask, inv, key)
        return loss, model_state
