"""Plain reference of granite-4.0-h (Hugging Face ``granitemoehybrid``)
with the duplex branch, in float32 ``jax.numpy`` at ``highest`` matmul
precision.

Written from the configuration file alone.  The embedding is multiplied by
``embedding_multiplier``.  Layer ``l`` is ``layer_types[l]``:

- ``mamba``: RMSNorm, then the Mamba-2 mixer: projections z, x, B, C, dt
  without bias (the source's one ``in_proj``, split); a depthwise causal
  convolution of width ``mamba_d_conv`` with bias, then SiLU, on x, B and
  C; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the SSD
  recurrence token by token,
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``, ``y_t = h_t C_t + D x_t``
  (head ``h`` reads group ``h // (heads / n_groups)`` of B and C); the
  gated RMSNorm ``rmsnorm(y * silu(z))`` over the inner width; the output
  projection;
- ``attention``: RMSNorm, causal grouped-query attention with no position
  embedding, scores scaled by ``attention_multiplier``.

Each mixer's output joins the residual times ``residual_multiplier``; then
RMSNorm and the MoE: a router over all ``router_experts`` experts, top-k
choices with the capacity of ``reference/transformer.py`` counted over all
of them and the kept gates renormalised over all kept choices, of which
this chip computes the choices of its ``num_local_experts`` experts from
``first_expert`` on; plus the shared gated MLP of width
``shared_intermediate_size``; their sum joins the residual times
``residual_multiplier``.  Final RMSNorm; the tied unembedding over the real
vocabulary, logits over ``logits_scaling``.  The loss and the branch are
``reference/transformer.py``'s.

Weights are drawn again from the seed, one layer at a time, and rounded to
the dtype the program stores them in.  ``lowp="fp8"`` is the control: it
rounds to float8 what the program holds in bfloat16, the operands of every
projection and expert matmul, the SSD recurrence's x, B and C and
attention's q, k and v (the operands of the program's SSD and attention
matmuls), the residual stream after every sublayer, and the branch's
activations and weights before their block floating point; the
recurrence's state and the softmax stay in float32.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import transformer as T
from chipbench.reference.transformer import F32, HI, mm, rmsnorm

MAMBA, ATTENTION = "mamba", "attention"


# --------------------------------------------------------------------------
# shapes of the program's parameter trees, from the configuration alone
# --------------------------------------------------------------------------

def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def period(cfg: dict) -> int:
    """Layers of the shortest pattern that repeats over the layers run: the
    program stacks each of its positions over the repeats."""
    kinds = layer_kinds(cfg)
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def dims(cfg: dict) -> dict:
    m = T.dims(cfg)
    heads, hdim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    m.update(inner=heads * hdim, mh=heads, mp=hdim,
             mn=cfg["mamba_d_state"], mg=cfg["mamba_n_groups"],
             conv=cfg["mamba_d_conv"], routed=cfg["router_experts"],
             first=cfg["first_expert"], fs=cfg["shared_intermediate_size"],
             period=period(cfg))
    assert m["inner"] == cfg["mamba_expand"] * m["d"]
    return m


def backbone_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, hd, f, fs = m["d"], m["hd"], m["f"], m["fs"]
    gn, inner = m["mg"] * m["mn"], m["inner"]
    rep = m["layers"] // m["period"]
    s = {"embed/table": (m["vpad"], d), "final_norm/scale": (d,)}
    for i, kind in enumerate(layer_kinds(cfg)[:m["period"]]):
        one = {"norm/scale": (d,), "mlp_norm/scale": (d,),
               "moe/router/w": (d, m["routed"]),
               "moe/wi": (m["e"], d, f), "moe/wg": (m["e"], d, f),
               "moe/wo": (m["e"], f, d),
               "moe/shared/wi/w": (d, fs), "moe/shared/wg/w": (d, fs),
               "moe/shared/wo/w": (fs, d)}
        if kind == MAMBA:
            one.update({
                "ssd/z_proj/w": (d, inner), "ssd/x_proj/w": (d, inner),
                "ssd/b_proj/w": (d, gn), "ssd/c_proj/w": (d, gn),
                "ssd/dt_proj/w": (d, m["mh"]), "ssd/out_proj/w": (inner, d),
                "ssd/conv_x/w": (m["conv"], inner), "ssd/conv_x/b": (inner,),
                "ssd/conv_b/w": (m["conv"], gn), "ssd/conv_b/b": (gn,),
                "ssd/conv_c/w": (m["conv"], gn), "ssd/conv_c/b": (gn,),
                "ssd/dt_bias": (m["mh"],), "ssd/A_log": (m["mh"],),
                "ssd/D": (m["mh"],), "ssd/norm/scale": (inner,)})
        elif kind == ATTENTION:
            one.update({"attn/wq/w": (d, m["h"] * hd),
                        "attn/wk/w": (d, m["kv"] * hd),
                        "attn/wv/w": (d, m["kv"] * hd),
                        "attn/wo/w": (m["h"] * hd, d)})
        else:
            raise ValueError(kind)
        s.update({f"stack/sub{i}/{k}": (rep,) + v for k, v in one.items()})
    return s


branch_shapes = T.branch_shapes


# --------------------------------------------------------------------------
# the layers
# --------------------------------------------------------------------------

def causal_conv(x, w, b):
    """x [B,T,C], w [K,C]: y_t = sum_i w_i x_{t-K+1+i} + b, then SiLU."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
                       + b)


def ssd_recurrence(x, dt, A, B, C):
    """x [B,T,H,P], dt [B,T,H], A [H], B/C [B,T,G,N] -> y [B,T,H,P], one
    token after another."""
    b, t, h, p = x.shape
    rep = h // B.shape[2]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        bt, ct = jnp.repeat(bt, rep, axis=1), jnp.repeat(ct, rep, axis=1)
        state = (state * jnp.exp(dtt * A)[..., None, None]
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, B.shape[-1]), F32),
                        tuple(a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1)


def mamba(p, u, cfg, lowp):
    m = dims(cfg)
    b, t, _ = u.shape
    z = mm(u, p["ssd/z_proj/w"], lowp)
    xs = causal_conv(mm(u, p["ssd/x_proj/w"], lowp), p["ssd/conv_x/w"],
                     p["ssd/conv_x/b"])
    Bs = causal_conv(mm(u, p["ssd/b_proj/w"], lowp), p["ssd/conv_b/w"],
                     p["ssd/conv_b/b"])
    Cs = causal_conv(mm(u, p["ssd/c_proj/w"], lowp), p["ssd/conv_c/w"],
                     p["ssd/conv_c/b"])
    dt = jax.nn.softplus(mm(u, p["ssd/dt_proj/w"], lowp) + p["ssd/dt_bias"])
    xh = xs.reshape(b, t, m["mh"], m["mp"])
    r = functools.partial(T.lowp_round, lowp=lowp)
    y = ssd_recurrence(r(xh), dt, -jnp.exp(p["ssd/A_log"]),
                       r(Bs.reshape(b, t, m["mg"], m["mn"])),
                       r(Cs.reshape(b, t, m["mg"], m["mn"])))
    y = (y + xh * p["ssd/D"][:, None]).reshape(b, t, m["inner"])
    y = rmsnorm(y * jax.nn.silu(z), p["ssd/norm/scale"], cfg["rms_norm_eps"])
    return mm(y, p["ssd/out_proj/w"], lowp)


def attention(p, u, cfg, lowp):
    """Causal GQA with no position embedding, scores times the multiplier
    (``T.causal_attention`` divides by sqrt(head_dim))."""
    m = dims(cfg)
    b, t, _ = u.shape
    scale = cfg["attention_multiplier"] * math.sqrt(m["hd"])
    q = mm(u, p["attn/wq/w"], lowp).reshape(b, t, m["h"], m["hd"]) * scale
    k = mm(u, p["attn/wk/w"], lowp).reshape(b, t, m["kv"], m["hd"])
    v = mm(u, p["attn/wv/w"], lowp).reshape(b, t, m["kv"], m["hd"])
    r = functools.partial(T.lowp_round, lowp=lowp)
    o = jax.lax.map(lambda qkv: T.causal_attention(*qkv), (r(q), r(k), r(v)))
    return mm(o.reshape(b, t, -1), p["attn/wo/w"], lowp)


def moe_share(p, xg, cfg, lowp):
    """xg [G,g,D]: every choice routed, placed and dropped over all the
    router's experts (as ``T.moe_groups`` does), the kept gates normalised
    over all kept choices; the output is the held experts' part alone."""
    G, g, d = xg.shape
    m = dims(cfg)
    routed, k, held, first = m["routed"], m["k"], m["e"], m["first"]
    cap = T.capacity(g, k, routed, cfg["capacity_factor"])
    gates = jax.nn.softmax(mm(xg, p["moe/router/w"], lowp), axis=-1)
    remaining, counts = gates, jnp.zeros((G, 1, routed), F32)
    picks = []
    for _ in range(k):
        idx = jnp.argmax(remaining, -1)                         # [G,g]
        onehot = jax.nn.one_hot(idx, routed, dtype=F32)
        pos = jnp.cumsum(onehot, 1) - 1.0 + counts
        counts = counts + jnp.sum(onehot, 1, keepdims=True)
        slot = jnp.take_along_axis(pos, idx[..., None], -1)[..., 0]
        gate = jnp.take_along_axis(remaining, idx[..., None], -1)[..., 0]
        keep = slot < cap
        picks.append((idx - first, slot.astype(jnp.int32), keep, gate))
        remaining = remaining * (1.0 - onehot)
    denom = jnp.maximum(sum(jnp.where(kp, gt, 0.0)
                            for _, _, kp, gt in picks), 1e-9)
    # which token fills each held expert's slot [G,held,cap]; g, a zero
    # row, where none does
    gi = jnp.arange(G)[:, None]
    owner = jnp.full((G, held, cap + 1), g, jnp.int32)
    tok = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32), (G, g))
    mine = [kp & (e >= 0) & (e < held) for e, _, kp, _ in picks]
    for (e, slot, _, _), mn in zip(picks, mine):
        owner = owner.at[gi, jnp.clip(e, 0, held - 1),
                         jnp.where(mn, slot, cap)].set(tok)
    xpad = jnp.concatenate([xg, jnp.zeros((G, 1, d), xg.dtype)], 1)
    buf = jax.vmap(lambda x, o: x[o])(xpad, owner[:, :, :cap])
    r = functools.partial(T.lowp_round, lowp=lowp)
    hid = jnp.einsum("gecd,edf->gecf", r(buf), r(p["moe/wi"]), precision=HI)
    gat = jnp.einsum("gecd,edf->gecf", r(buf), r(p["moe/wg"]), precision=HI)
    out = jnp.einsum("gecf,efd->gecd", r(jax.nn.silu(gat) * hid),
                     r(p["moe/wo"]), precision=HI)
    y = jnp.zeros_like(xg)
    for (e, slot, _, gate), mn in zip(picks, mine):
        got = out[gi, jnp.clip(e, 0, held - 1), jnp.minimum(slot, cap - 1)]
        y = y + jnp.where(mn, gate / denom, 0.0)[..., None] * got
    return y


def moe(p, u, cfg, lowp):
    """u [B,T,D], dispatched as one flat batch in groups of
    ``moe_group_size`` tokens, plus the shared expert."""
    b, t, d = u.shape
    flat = u.reshape(b * t, d)
    n = flat.shape[0]
    g = min(cfg["moe_group_size"], n)
    n_pad = -(-n // g) * g
    flat = jnp.pad(flat, ((0, n_pad - n), (0, 0)))
    y = moe_share(p, flat.reshape(n_pad // g, g, d), cfg, lowp)
    y = y.reshape(n_pad, d)[:n].reshape(b, t, d)
    shared = mm(jax.nn.silu(mm(u, p["moe/shared/wg/w"], lowp))
                * mm(u, p["moe/shared/wi/w"], lowp), p["moe/shared/wo/w"],
                lowp)
    return y + shared


def layer(p, h, cfg, kind, lowp=None):
    eps, mult = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    r = functools.partial(T.lowp_round, lowp=lowp)
    u = rmsnorm(h, p["norm/scale"], eps)
    mix = mamba if kind == MAMBA else attention
    h = r(h + mix(p, u, cfg, lowp) * mult)
    return r(h + moe(p, rmsnorm(h, p["mlp_norm/scale"], eps), cfg, lowp)
             * mult)


@contextlib.contextmanager
def _rounded_before_bfp(lowp):
    """``T.bfp`` of the operand rounded by ``lowp`` first, while the branch
    is traced (``T.branch_correction`` reads ``T.bfp`` when it runs)."""
    if lowp is None:
        yield
        return
    bfp = T.bfp
    T.bfp = lambda x, **kw: bfp(T.lowp_round(x, lowp), **kw)
    try:
        yield
    finally:
        T.bfp = bfp


# --------------------------------------------------------------------------
# the model, drawn layer by layer from the seed
# --------------------------------------------------------------------------

class Reference(T.Reference):
    """``T.Reference`` with this model's layers.  ``table`` is the
    unembedding as the loss sees it (the tied table over
    ``logits_scaling``); ``embed`` is the table itself."""

    def __init__(self, cfg: dict, seed: int, stored=jnp.float32,
                 lowp: str | None = None):
        self.cfg, self.lowp = cfg, lowp
        self.key = weights.seed_key(seed)
        self.stored = stored
        self.shapes = backbone_shapes(cfg)
        self.m = dims(cfg)
        init = cfg["init"]
        per = self.m["period"]

        # weights.layer_slice's draw, with each leaf's key made outside the
        # program: the layers of one kind share one program
        @functools.partial(jax.jit, static_argnums=(1,))
        def draw(keys, leaves):
            return {rel: weights._draw(k, kind, scale, shape)
                    .astype(stored).astype(F32)
                    for k, (rel, kind, scale, shape) in zip(keys, leaves)}

        def draw_layer(i):
            pre = f"stack/sub{i % per}/"
            paths = [p for p in self.shapes if p.startswith(pre)]
            keys = [jax.random.fold_in(weights.leaf_key(
                self.key, "backbone", p), i // per) for p in paths]
            return draw(keys, tuple(
                (p[len(pre):],) + weights._rule(init, p)
                + (self.shapes[p][1:],) for p in paths))

        self._draw_layer = draw_layer
        self._layer = jax.jit(
            lambda p, h, kind, lowp: layer(p, h, cfg, kind, lowp),
            static_argnums=(2, 3))
        table = weights.leaf(self.key, "backbone", "embed/table",
                             self.shapes["embed/table"], init)
        self.embed = jax.jit(lambda t: t[:self.m["vocab"]].astype(stored)
                             .astype(F32))(table)
        self.table = self.embed / cfg["logits_scaling"]
        self.final_scale = weights.leaf(self.key, "backbone",
                                        "final_norm/scale", (self.m["d"],),
                                        init).astype(stored).astype(F32)

    def train(self, batches, steps: int) -> dict:
        """``T.Reference.train``; the control also rounds the branch's
        activations and weights to float8 before their block floating
        point, as the program holds them in bfloat16 there."""
        with _rounded_before_bfp(self.lowp):
            return super().train(batches, steps)

    def forward(self, batches, decode_from=None, taps=None):
        """tokens [B,T] of each batch -> (emb, final hidden, pooled taps or
        None) of each, layer by layer."""
        if decode_from is not None:
            raise NotImplementedError("the hybrid reference serves nothing")
        mult = self.cfg["embedding_multiplier"]
        hs = [T.lowp_round(self.embed[jnp.asarray(t)] * mult, self.lowp)
              for t in batches]
        embs, tapped = list(hs), [[] for _ in batches]
        want = set() if taps is None else set(int(i) for i in taps)
        r = self.cfg["duplex"]["pool_factor"] if taps is not None else 1
        for i, kind in enumerate(layer_kinds(self.cfg)):
            p = self._draw_layer(i)
            for j, h in enumerate(hs):
                hs[j] = self._layer(p, h, kind, self.lowp)
                if i in want:
                    tapped[j].append(T.pool(hs[j], r))
            del p
        eps = self.cfg["rms_norm_eps"]
        return [(e, rmsnorm(h, self.final_scale, eps),
                 jnp.stack(t) if taps is not None else None)
                for e, h, t in zip(embs, hs, tapped)]
