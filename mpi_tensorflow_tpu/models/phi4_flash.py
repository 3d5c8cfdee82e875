"""Decoder-hybrid-decoder (SambaY, arXiv:2507.06607) with differential
attention: the ``phi4flash`` family.

A third decoder family beside ``CausalLm`` and ``MlaMoeLm``.  Every
layer is ``h += Mixer(LN1(h)); h += MLP(LN2(h))`` (LayerNorm with scale
and bias, fused SwiGLU), a final LayerNorm, a head tied to the
embedding, no positional encoding.  The mixer goes by depth
(``Phi4FlashConfig.layer_kind``), with ``half = layers // 2``:

- ``i < half``: the self-decoder's Samba part, even ``mamba`` (Mamba-1,
  arXiv:2312.00752), odd ``window`` (differential attention over the
  last ``sliding_window`` keys);
- ``i == half``: ``mamba`` whose scan output ``y`` (before the gate) is
  the MEMORY;
- ``i == half + 1``: ``full`` causal differential attention whose K/V
  are THE cache of the cross-decoder;
- after it, even ``gmu`` (gated memory unit: ``(m * silu(x W_g)) W_o``
  on the memory of the same token), odd ``cross`` (queries only,
  attending the full layer's K/V).

Serving only.  ``forward_paged`` has ``CausalLm.forward_paged``'s
contract, plus the two things this family needs of the engine
(``slot_state`` tells it to pass them):

- ``slots`` (B,): which slot of the engine each row is.  State that is
  not a block reference lives by slot: a Mamba layer's recurrent state
  ``(slots + 1, d_state, d_inner)`` float32 and the last ``d_conv - 1``
  rows before its convolution, and a window layer's K/V ring (ops/
  diff_attention).  Row ``slots`` (the last) takes padding rows' writes.
  A row that starts at position 0 starts from zero state: a slot that
  changes hands, or a sequence restarted after eviction, re-prefills
  from 0, so nothing has to reset a slot.
- ``take`` (B,): the one lane of each row whose logits are wanted, or
  -1 for none.  Layers up to ``half`` and the full layer's K/V write
  run on every lane; the full layer's attention and everything after it
  run ONLY on the taken lane (the architecture's linear-time prefill),
  and not at all where no row takes one (a prompt's non-final chunk).
  Logits come back ``(B, 1, V)``.  ``take=None`` is the all-lanes form.

``pool_leaves`` declares the three kinds of cache side by side
(serving/paged_cache: ``*_slot`` leaves are per slot, ``win_*`` of them
window stores): per-slot state for the mamba layers, rings for the
window layers, ONE paged K/V pool for the full layer, nothing for gmu
and cross layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mpi_tensorflow_tpu.ops import diff_attention as da
from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.utils import engagement


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    # Mamba-1's defaults (arXiv:2312.00752): the published config has
    # no keys for them
    d_state: int = 16
    d_conv: int = 4
    mamba_expand: int = 2
    dtype: Any = jnp.float32

    def __post_init__(self):
        H, Hkv = self.num_attention_heads, self.num_key_value_heads
        if self.mb_per_layer != 2 or self.num_hidden_layers % 2 \
                or self.num_hidden_layers < 4:
            raise ValueError(
                "the layout is built for mb_per_layer 2 and an even "
                "depth of at least 4 (mamba | window ... mamba | full | "
                "gmu | cross ...)")
        if H % 4 or Hkv * 2 != H or self.hidden_size % H:
            raise ValueError(
                f"differential attention pairs heads: {H} query heads "
                f"need {H // 2} key/value heads and a multiple of 4, "
                f"got {Hkv}")

    # what the serving engine asks of any model's config
    pos_kind = "none"

    @property
    def max_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    def layer_kind(self, i: int) -> str:
        half = self.num_hidden_layers // 2
        if i <= half:
            return "mamba" if i % 2 == 0 or i == half else "window"
        if i == half + 1:
            return "full"
        return "gmu" if (i - half) % 2 == 0 else "cross"

    @property
    def tp_refusal(self) -> str:
        return ("a Mamba layer's scan runs over all of d_inner with one "
                "state per sequence and the rings are addressed by slot, "
                "neither is spread over a mesh yet: serve this model "
                "with tp 1")

    def serve_refusal(self, serve) -> Optional[str]:
        """What of ``serve`` this family cannot do yet, in words (None:
        nothing).  ``engine.check_model`` raises it."""
        state = ("a sequence's state-space state and window rings are "
                 "not block references")
        if serve.kv_tier != "off":
            return (f"kv_tier {serve.kv_tier}: {state}, so a demoted "
                    f"block cannot bring them back; serve this model "
                    f"with kv_tier off")
        if serve.prefix_gen != "off":
            return (f"prefix_gen on: {state} and have no snapshot at a "
                    f"generated block's edge; serve this model with "
                    f"prefix_gen off")
        if serve.prefix_cache != "off":
            return (f"prefix_cache on: {state} and have no snapshot at a "
                    f"cached prefix's end, so a hit could not resume "
                    f"from it; serve this model with prefix_cache off")
        if serve.speculative != "off":
            return (f"speculative {serve.speculative}: a rejected draft "
                    f"would have to roll the state-space state back, "
                    f"and verify needs logits of every lane; serve this "
                    f"model with speculative off")
        if serve.mixed_batch != "off":
            return ("mixed_batch on: the fused dispatch takes logits of "
                    "every lane, and this family runs its cross-decoder "
                    "on one lane a row; serve this model with "
                    "mixed_batch off")
        if serve.kv_dtype != "fp32":
            return _kv_refusal(serve.kv_dtype)
        return None


#: the CPU size (tests, ``python -m mpi_tensorflow_tpu.serving --tiny``)
TINY = Phi4FlashConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=8, num_attention_heads=4,
                       num_key_value_heads=2, sliding_window=8,
                       max_position_embeddings=512)


def _kv_refusal(kv_dtype: str) -> str:
    return (f"kv_dtype {kv_dtype}: the rings and the grouped-head pool "
            f"have no quantised form yet; serve this model with "
            f"kv_dtype fp32")


def layernorm(x, p, eps: float):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def mlp(p, x):
    """``[g | u] = x W_1``, ``(u * silu(g)) W_2``."""
    dt = x.dtype
    g, u = jnp.split(jnp.einsum("...e,ef->...f", x, p["w1"].astype(dt)),
                     2, axis=-1)
    return jnp.einsum("...f,fe->...e", u * jax.nn.silu(g),
                      p["w2"].astype(dt))


def gmu(p, x, memory):
    """Gated memory unit: ``(m * silu(x W_g)) W_o`` on the memory of the
    same tokens."""
    dt = x.dtype
    g = jnp.einsum("bse,ef->bsf", x, p["w_g"].astype(dt))
    return jnp.einsum("bsf,fe->bse", memory * jax.nn.silu(g),
                      p["w_o"].astype(dt))


def selective_scan(s0, delta, u, Bm, Cm, A):
    """``s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t``,
    ``y_t = s_t . C_t``: float32, state ``(B, N, Di)``, one token after
    another (eight to a loop body).  ``delta`` 0 leaves the state as it
    was."""
    def step(s, x):
        d, du, b, c = x
        s = jnp.exp(d[:, None, :] * A[None]) * s \
            + du[:, None, :] * b[:, :, None]
        return s, jnp.sum(s * c[:, :, None], axis=1)

    S = delta.shape[1]
    xs = (delta, delta * u, Bm, Cm)
    if S == 1:
        s, y = step(s0, tuple(x[:, 0] for x in xs))
        return s, y[:, None]
    s, ys = lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in xs),
                     unroll=min(8, S))
    return s, jnp.moveaxis(ys, 0, 1)


@dataclasses.dataclass(frozen=True)
class Phi4FlashLm:
    cfg: Phi4FlashConfig

    #: the engine passes ``slots`` and ``take`` to ``forward_paged``
    slot_state = True
    #: ... and decode tables at full width: one layer of 32 reads them,
    #: through a kernel whose grid follows the live blocks
    full_tables = True

    # ---------------- weights ----------------

    def init(self, key, std: float = 0.02):
        """normal(0, std) matrices, zero biases, unit LayerNorm scales;
        Mamba as its authors initialise it: ``A_log = log(1..d_state)``,
        ``D = 1``, ``b_dt`` the inverse softplus of a step log-uniform
        in [1e-3, 1e-1], ``w_dt`` uniform in +- dt_rank^-0.5, the
        convolution uniform in +- d_conv^-0.5; the lambda vectors
        normal(0, 0.1), the sub-layer norm's scale 1."""
        c = self.cfg
        E, F, D = c.hidden_size, c.intermediate_size, c.head_dim
        Hq, KW = c.num_attention_heads, c.kv_width
        Di, N, R, K = c.d_inner, c.d_state, c.dt_rank, c.d_conv

        def mat(k, *shape, s=std):
            return jax.random.normal(k, shape, jnp.float32) * s

        def uni(k, shape, bound):
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

        def ln():
            return {"scale": jnp.ones((E,)), "bias": jnp.zeros((E,))}

        def lambdas(k):
            ks = jax.random.split(k, 4)
            return {n: mat(kk, D, s=0.1) for n, kk in zip(
                ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), ks)}

        def mixer(k, kind):
            ks = jax.random.split(k, 6)
            if kind == "mamba":
                step = jnp.exp(jax.random.uniform(ks[4], (Di,))
                               * (jnp.log(0.1) - jnp.log(1e-3))
                               + jnp.log(1e-3))
                return {"w_in": mat(ks[0], E, 2 * Di),
                        "conv_w": uni(ks[1], (K, Di), K ** -0.5),
                        "conv_b": jnp.zeros((Di,)),
                        "w_x": mat(ks[2], Di, R + 2 * N),
                        "w_dt": uni(ks[3], (R, Di), R ** -0.5),
                        "b_dt": step + jnp.log(-jnp.expm1(-step)),
                        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                            1, N + 1, dtype=jnp.float32))[:, None],
                            (N, Di)),
                        "d": jnp.ones((Di,)),
                        "w_out": mat(ks[5], Di, E)}
            if kind == "gmu":
                return {"w_g": mat(ks[0], E, Di), "w_o": mat(ks[1], Di, E)}
            wide = Hq * D + (0 if kind == "cross" else 2 * KW)
            return {"w_qkv": mat(ks[0], E, wide),
                    "b_qkv": jnp.zeros((wide,)),
                    "w_o": mat(ks[1], Hq * D, E), "b_o": jnp.zeros((E,)),
                    "subln": jnp.ones((2 * D,)), **lambdas(ks[2])}

        def layer(k, i):
            km, k1, k2 = jax.random.split(k, 3)
            return {"ln1": ln(), "ln2": ln(),
                    "mixer": mixer(km, c.layer_kind(i)),
                    "mlp": {"w1": mat(k1, E, 2 * F), "w2": mat(k2, F, E)}}

        k_tok, k_layers = jax.random.split(key)
        return {"tok_emb": mat(k_tok, c.vocab_size, E),
                "final_ln": ln(),
                "layers": [layer(k, i) for i, k in enumerate(
                    jax.random.split(k_layers, c.num_hidden_layers))]}

    # ---------------- what the engine asks ----------------

    def pool_leaves(self, num_blocks: int, block_size: int,
                    kv_dtype: str = "fp32", max_slots: int = 0) -> list:
        """Per layer ``{name: ShapeDtypeStruct}``: state by slot for a
        mamba layer, K and V rings by slot for a window layer, the paged
        K/V pool for the full layer, nothing for gmu and cross layers."""
        c = self.cfg
        if kv_dtype != "fp32":
            raise ValueError(_kv_refusal(kv_dtype))
        rows = max_slots + 1

        def leaf(*shape, dtype=c.dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        kinds = {
            "mamba": {"ssm_slot": leaf(rows, c.d_state, c.d_inner,
                                       dtype=jnp.float32),
                      "conv_slot": leaf(rows, c.d_conv - 1, c.d_inner)},
            "window": {"win_k_slot": leaf(rows, c.sliding_window,
                                          c.kv_width),
                       "win_v_slot": leaf(rows, c.sliding_window,
                                          c.kv_width)},
            "full": {"k": leaf(num_blocks, block_size, c.kv_width),
                     "v": leaf(num_blocks, block_size, c.kv_width)},
            "gmu": {}, "cross": {}}
        return [dict(kinds[c.layer_kind(i)])
                for i in range(c.num_hidden_layers)]

    def resolve_kernel(self, choice: str, block_size: int,
                       prefill_chunk: int) -> str:
        c = self.cfg
        return da.resolve_kernel(choice, c.dtype, c.num_attention_heads,
                                 c.head_dim, block_size, c.sliding_window)

    def dispatch_extra(self, kind: str, starts, counts, taken: int) -> dict:
        """What one dispatch obliges of this family's caches, from the
        host's scheduler state (``starts``: each row's position before
        the dispatch; ``counts``: its real tokens; ``taken``: lanes whose
        logits were taken): tokens through the scans, keys the window
        layers attended (a layer), keys the full layer's cache was read
        for (a pass), and lanes the cross-decoder was skipped on."""
        import numpy as np

        W = self.cfg.sliding_window
        lo = np.asarray(starts, np.int64)
        hi = lo + np.asarray(counts, np.int64)
        # a query at position p sees min(p + 1, W) keys
        ramp = np.minimum(hi, W)
        window = np.where(lo < ramp, (ramp * (ramp + 1) - lo * (lo + 1))
                          // 2, 0) + np.maximum(0, hi - np.maximum(lo, W)) * W
        if kind == "decode":
            full = int(hi.sum())
        else:
            full = int(hi[0]) if taken else 0
        tokens = int(sum(counts))
        return {"scanned": tokens, "window_keys": int(window.sum()),
                "full_keys": int(full),
                "skipped_lanes": tokens - int(taken)}

    # ---------------- the mixers ----------------

    def _mamba(self, mp, x, s0, tail, valid):
        """``x`` (B, S, E) normed input, state ``s0`` (B, N, Di) f32,
        ``tail`` (B, K - 1, Di) the rows before the convolution ->
        (output, memory ``y``, new state, new tail).  Invalid lanes (a
        suffix) move neither."""
        c = self.cfg
        dt = x.dtype
        Di, N, R, K = c.d_inner, c.d_state, c.dt_rank, c.d_conv
        S = x.shape[1]
        u, z = jnp.split(jnp.einsum("bse,ef->bsf", x,
                                    mp["w_in"].astype(dt)), 2, axis=-1)
        ext = jnp.concatenate([tail.astype(dt), u], axis=1)
        conv = mp["conv_b"].astype(jnp.float32) + sum(
            ext[:, k:k + S].astype(jnp.float32)
            * mp["conv_w"][k].astype(jnp.float32) for k in range(K))
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        new_tail = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(
            e, n, K - 1, 0))(ext, n_valid)
        uc = jax.nn.silu(conv)                            # f32
        dbc = jnp.einsum("bsf,fr->bsr", uc.astype(dt),
                         mp["w_x"].astype(dt)).astype(jnp.float32)
        delta = jax.nn.softplus(
            jnp.einsum("bsr,rf->bsf", dbc[..., :R].astype(dt),
                       mp["w_dt"].astype(dt)).astype(jnp.float32)
            + mp["b_dt"].astype(jnp.float32))
        delta = jnp.where(valid[..., None], delta, 0.0)
        A = -jnp.exp(mp["a_log"].astype(jnp.float32))
        with jax.named_scope("ssm_scan"):
            s, y = selective_scan(s0, delta, uc, dbc[..., R:R + N],
                                  dbc[..., R + N:], A)
        y = (y + mp["d"].astype(jnp.float32) * uc).astype(dt)
        out = jnp.einsum("bsf,fe->bse", y * jax.nn.silu(z),
                         mp["w_out"].astype(dt))
        return out, y, s, new_tail

    def _lambda(self, ap, depth: int):
        f = jnp.float32
        return jnp.exp(jnp.sum(ap["lambda_q1"].astype(f)
                               * ap["lambda_k1"].astype(f))) \
            - jnp.exp(jnp.sum(ap["lambda_q2"].astype(f)
                              * ap["lambda_k2"].astype(f))) \
            + da.lambda_init(depth)

    def _project(self, ap, x):
        """``x`` (B, S, E) -> q (B, S, Hq, D) and, where the layer has
        its own, K and V token rows (B, S, KW)."""
        c = self.cfg
        dt = x.dtype
        qkv = jnp.einsum("bse,ef->bsf", x, ap["w_qkv"].astype(dt)) \
            + ap["b_qkv"].astype(dt)
        n = c.num_attention_heads * c.head_dim
        q = qkv[..., :n].reshape(x.shape[:2] + (c.num_attention_heads,
                                                c.head_dim))
        if qkv.shape[-1] == n:
            return q, None, None
        return q, qkv[..., n:n + c.kv_width], qkv[..., n + c.kv_width:]

    def _attn_out(self, ap, a, depth: int, dt):
        c = self.cfg
        o = da.combine(a, self._lambda(ap, depth), da.lambda_init(depth),
                       ap["subln"], c.layer_norm_eps, dt)
        return jnp.einsum("bsf,fe->bse", o, ap["w_o"].astype(dt)) \
            + ap["b_o"].astype(dt)

    @property
    def _scale(self) -> float:
        return self.cfg.head_dim ** -0.5

    def _window(self, ap, x, pool, slots, pos, valid, depth, kernel,
                ring_work):
        """A window layer over its rings: (output, new pool entry)."""
        c = self.cfg
        W = c.sliding_window
        q, k, v = self._project(ap, x)
        S = x.shape[1]
        rk, rv = pool["win_k_slot"], pool["win_v_slot"]
        new = {"win_k_slot": paged_ops.write_ring(rk, k, slots, pos, valid),
               "win_v_slot": paged_ops.write_ring(rv, v, slots, pos, valid)}
        if S == 1 and kernel != "xla":
            # the token's own write first; the ring then holds exactly
            # the keys it may see
            a = da.decode_attend(
                q[:, 0], new["win_k_slot"], new["win_v_slot"],
                slots[:, None], jnp.minimum(pos[:, 0], W - 1), self._scale,
                kernel=kernel, work=ring_work)[:, None]
        else:
            # the rings as they stood, then the chunk's own keys
            held = da.ring_positions(pos[:, 0], W)              # (B, W)
            kpos = jnp.concatenate(
                [held, jnp.where(valid, pos, -1)], axis=1)      # (B, W+S)
            vis = (kpos[:, None, :] >= 0) \
                & (kpos[:, None, :] <= pos[:, :, None]) \
                & (kpos[:, None, :] > pos[:, :, None] - W)
            a = da.attention_xla(
                q, jnp.concatenate([rk[slots], k.astype(rk.dtype)], 1),
                jnp.concatenate([rv[slots], v.astype(rv.dtype)], 1),
                vis, self._scale)
        return self._attn_out(ap, a, depth, x.dtype), new

    def _paged_attn(self, ap, x, pool, tables, qpos, depth, kernel, work):
        """Differential attention of ``x`` (B, T, E) at positions
        ``qpos`` (B, T) over the full layer's pool (already written)."""
        q, _, _ = self._project(ap, x)
        if x.shape[1] == 1:
            a = da.decode_attend(q[:, 0], pool["k"], pool["v"], tables,
                                 qpos[:, 0], self._scale, kernel=kernel,
                                 work=work)[:, None]
        else:
            k = da.gather_paged(pool["k"], tables)
            vis = jnp.arange(k.shape[1])[None, None, :] <= qpos[:, :, None]
            a = da.attention_xla(q, k, da.gather_paged(pool["v"], tables),
                                 vis, self._scale)
        return self._attn_out(ap, a, depth, x.dtype)

    # ---------------- serving ----------------

    def forward_paged(self, params, tokens, pools, block_tables, lengths,
                      valid=None, kernel: str = "xla", reduce=None,
                      slots=None, take=None):
        """``CausalLm.forward_paged``'s contract over the three caches:
        row ``b`` of ``tokens`` (B, S_in) sits at positions
        ``[lengths[b], lengths[b] + S_in)`` of the sequence in slot
        ``slots[b]``.  Returns (fp32 logits, updated pools): logits of
        every lane (B, S_in, V) with ``take=None``, else of lane
        ``take[b]`` alone (B, 1, V) (zeros where no row takes one)."""
        if reduce is not None:
            raise ValueError(self.cfg.tp_refusal)
        if slots is None:
            raise ValueError("this family keeps state by slot: "
                             "forward_paged needs slots=")
        c = self.cfg
        dt = c.dtype
        B, S = tokens.shape
        half = c.num_hidden_layers // 2
        lengths = jnp.asarray(lengths, jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)
        if valid is None:
            valid = jnp.ones((B, S), bool)
        engagement.record("diff_attention", kernel)
        fast = S == 1 and kernel != "xla"
        ring_work = da.decode_work(
            jnp.minimum(lengths, c.sliding_window - 1), c.sliding_window,
            1) if fast else None
        fresh = (lengths == 0)[:, None, None]
        layers = params["layers"]
        h = params["tok_emb"][tokens].astype(dt)
        new_pools = []
        memory = None
        # the self-decoder, every lane
        for i in range(half + 1):
            lp, pool = layers[i], pools[i]
            x = layernorm(h, lp["ln1"], c.layer_norm_eps)
            if c.layer_kind(i) == "mamba":
                s0 = jnp.where(fresh, 0.0, pool["ssm_slot"][slots])
                tail = jnp.where(fresh, 0, pool["conv_slot"][slots])
                o, y, s, tail = self._mamba(lp["mixer"], x, s0, tail,
                                            valid)
                new_pools.append({
                    "ssm_slot": pool["ssm_slot"].at[slots].set(s),
                    "conv_slot": pool["conv_slot"].at[slots].set(
                        tail.astype(pool["conv_slot"].dtype))})
                memory = y
            else:
                with jax.named_scope("window_attn"):
                    o, entry = self._window(lp["mixer"], x, pool, slots,
                                            pos, valid, i, kernel,
                                            ring_work)
                new_pools.append(entry)
            h = h + o
            h = h + mlp(lp["mlp"], layernorm(h, lp["ln2"],
                                             c.layer_norm_eps))
        # the full layer's K/V, every lane: THE cache of what follows
        full = half + 1
        fp = layers[full]["mixer"]
        x = layernorm(h, layers[full]["ln1"], c.layer_norm_eps)
        _, k, v = self._project(fp, x)
        cache = {"k": da.write_paged(pools[full]["k"], k, block_tables,
                                     pos, valid),
                 "v": da.write_paged(pools[full]["v"], v, block_tables,
                                     pos, valid)}
        new_pools.append(cache)
        new_pools += [{} for _ in range(full + 1, c.num_hidden_layers)]

        def cross_decoder(h, memory, qpos):
            work = da.decode_work(qpos[:, 0], cache["k"].shape[1],
                                  block_tables.shape[1]) \
                if h.shape[1] == 1 and kernel != "xla" else None
            for i in range(full, c.num_hidden_layers):
                lp, kind = layers[i], c.layer_kind(i)
                x = layernorm(h, lp["ln1"], c.layer_norm_eps)
                if kind == "gmu":
                    with jax.named_scope("gmu"):
                        o = gmu(lp["mixer"], x, memory)
                else:
                    with jax.named_scope(f"{kind}_attn"):
                        o = self._paged_attn(lp["mixer"], x, cache,
                                             block_tables, qpos, i,
                                             kernel, work)
                h = h + o
                h = h + mlp(lp["mlp"], layernorm(h, lp["ln2"],
                                                 c.layer_norm_eps))
            h = layernorm(h, params["final_ln"], c.layer_norm_eps)
            return jnp.einsum("bse,ve->bsv", h,
                              params["tok_emb"].astype(dt)
                              ).astype(jnp.float32)

        if take is None or S == 1:
            return cross_decoder(h, memory, pos), new_pools
        take = jnp.asarray(take, jnp.int32)
        lane = jnp.maximum(take, 0)[:, None]

        def pick(x):
            return jnp.take_along_axis(x, lane[:, :, None], axis=1)

        logits = lax.cond(
            jnp.any(take >= 0),
            lambda: cross_decoder(pick(h), pick(memory),
                                  jnp.take_along_axis(pos, lane, axis=1)),
            lambda: jnp.zeros((B, 1, c.vocab_size), jnp.float32))
        return logits, new_pools

    def forward(self, params, tokens):
        """Plain causal forward of whole sequences ``tokens`` (B, S): no
        cache, masks written out.  For tests."""
        c = self.cfg
        dt = c.dtype
        B, S = tokens.shape
        half = c.num_hidden_layers // 2
        valid = jnp.ones((B, S), bool)
        i_, j_ = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        causal = jnp.broadcast_to(j_ <= i_, (B, S, S))
        window = causal & (j_ > i_ - c.sliding_window)
        h = params["tok_emb"][tokens].astype(dt)
        memory = cache = None
        for i, lp in enumerate(params["layers"]):
            kind, mp = c.layer_kind(i), lp["mixer"]
            x = layernorm(h, lp["ln1"], c.layer_norm_eps)
            if kind == "mamba":
                o, memory, _, _ = self._mamba(
                    mp, x, jnp.zeros((B, c.d_state, c.d_inner)),
                    jnp.zeros((B, c.d_conv - 1, c.d_inner), dt), valid)
            elif kind == "gmu":
                o = gmu(mp, x, memory)
            else:
                q, k, v = self._project(mp, x)
                if kind == "full":
                    cache = (k, v)
                elif kind == "cross":
                    k, v = cache
                a = da.attention_xla(
                    q, k, v, window if kind == "window" else causal,
                    self._scale)
                o = self._attn_out(mp, a, i, dt)
            h = h + o
            h = h + mlp(lp["mlp"], layernorm(h, lp["ln2"],
                                             c.layer_norm_eps))
        h = layernorm(h, params["final_ln"], c.layer_norm_eps)
        return jnp.einsum("bse,ve->bsv", h,
                          params["tok_emb"].astype(dt)).astype(jnp.float32)
