"""Plain reference of a pre-norm decoder-only transformer with the duplex
branch, in float32 ``jax.numpy`` at ``highest`` matmul precision.

It follows the equations the program runs, written out from the
configuration file alone: RMSNorm (eps from the file), rotary embedding of
the split-half kind, causal grouped-query attention scaled by
``1/sqrt(head_dim)``, a gated SiLU MLP, or a router over experts that keeps
each group's tokens within a capacity, and a tied unembedding over the real
vocabulary.  The training loss is next-token cross-entropy plus
``z_loss * logsumexp**2``; the duplex branch is a stack of reversible blocks
over mean-pooled streams with block floating-point operands, and SGD with
momentum, weight decay and a clipped global norm updates it.

Nothing here comes from the program.  Weights are drawn again from the seed
(``chipbench.weights``), one layer at a time, and rounded to the dtype the
program stores them in.  ``lowp="fp8"`` computes every matmul on operands
rounded to float8 e4m3 with one scale per tensor, and their gradients on
e5m2: the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_CHUNK = 512
LOSS_CHUNK = 1024


# --------------------------------------------------------------------------
# shapes of the program's parameter trees, from the configuration alone
# --------------------------------------------------------------------------

def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    pad = cfg["vocab_pad_multiple"]
    return {"d": d, "h": h, "kv": kv, "hd": hd, "f": cfg["intermediate_size"],
            "e": cfg.get("num_local_experts", 0),
            "k": cfg.get("num_experts_per_tok", 0),
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "vpad": -(-cfg["vocab_size"] // pad) * pad}


def backbone_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, L, hd = m["d"], m["layers"], m["hd"]
    s = {"embed/table": (m["vpad"], d), "final_norm/scale": (d,)}
    pre = "stack/sub0/"
    s.update({pre + "norm/scale": (L, d), pre + "mlp_norm/scale": (L, d),
              pre + "attn/wq/w": (L, d, m["h"] * hd),
              pre + "attn/wk/w": (L, d, m["kv"] * hd),
              pre + "attn/wv/w": (L, d, m["kv"] * hd),
              pre + "attn/wo/w": (L, m["h"] * hd, d)})
    if m["e"]:
        e, f = m["e"], m["f"]
        s.update({pre + "moe/router/w": (L, d, e),
                  pre + "moe/wi": (L, e, d, f), pre + "moe/wg": (L, e, d, f),
                  pre + "moe/wo": (L, e, f, d)})
    else:
        f = m["f"]
        s.update({pre + "mlp/wi/w": (L, d, f), pre + "mlp/wg/w": (L, d, f),
                  pre + "mlp/wo/w": (L, f, d)})
    return s


def branch_shapes(cfg: dict) -> dict:
    x, d = cfg["duplex"], cfg["hidden_size"]
    nb, db = x["n_blocks"], x["d_branch"]
    ff = db * x["branch_ff_mult"]
    s = {"in_proj1/w": (d, db), "in_proj2/w": (d, db),
         "tap_proj/w": (nb, d, db), "out_proj/w": (2 * db, d)}
    for n in ("wq", "wk", "wv", "wo"):
        s[f"blocks/f1/attn/{n}/w"] = (nb, db, db)
    s.update({"blocks/f2/mlp/wi/w": (nb, db, ff),
              "blocks/f2/mlp/wg/w": (nb, db, ff),
              "blocks/f2/mlp/wo/w": (nb, ff, db)})
    return s


def tap_indices(n_layers: int, n_blocks: int) -> np.ndarray:
    """Evenly spaced backbone layers feeding the branch blocks."""
    return np.round(np.linspace(0, n_layers - 1, n_blocks)).astype(np.int32)


# --------------------------------------------------------------------------
# numerics
# --------------------------------------------------------------------------

def _scaled(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient is
    rounded likewise to e5m2, as float8 training does."""
    return _scaled(x, jnp.float8_e4m3fn)


fp8.defvjp(lambda x: (_scaled(x, jnp.float8_e4m3fn), None),
           lambda _, g: (_scaled(g, jnp.float8_e5m2),))


def lowp_round(x: jax.Array, lowp: str | None) -> jax.Array:
    if lowp is None:
        return x
    if lowp != "fp8":
        raise ValueError(lowp)
    return fp8(x)


def mm(a, b, lowp=None):
    return jnp.matmul(lowp_round(a, lowp), lowp_round(b, lowp), precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x [..., T, H, hd], pos [T]; split-half rotary embedding."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v):
    """One sequence: q [T,H,hd], k/v [T,KV,hd] -> [T,H,hd].  Query chunk
    ``i`` meets only keys ``< end of chunk``; the mask does the rest."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    outs = []
    for lo in range(0, t, Q_CHUNK):
        hi = min(lo + Q_CHUNK, t)
        s = jnp.einsum("qhe,khe->hqk", q[lo:hi], k[:hi],
                       precision=HI) / math.sqrt(hd)
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khe->qhe", w, v[:hi], precision=HI))
    return jnp.concatenate(outs, 0)


def capacity(group: int, top_k: int, experts: int, factor: float) -> int:
    c = int(math.ceil(group * top_k / experts * factor))
    return max(4, -(-c // 4) * 4)


def moe_groups(p, xg, cfg, lowp):
    """xg [G,g,D]: top-k routing with a per-group capacity.  Choices are
    placed round by round (every token's first choice, then every second
    choice, ...) in token order; a choice past its expert's capacity is
    dropped, and the kept gates are renormalised to sum to one."""
    G, g, d = xg.shape
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    cap = capacity(g, k, e, cfg["capacity_factor"])
    gates = jax.nn.softmax(mm(xg, p["moe/router/w"], lowp), axis=-1)
    remaining, counts = gates, jnp.zeros((G, 1, e), F32)
    picks = []
    for _ in range(k):
        idx = jnp.argmax(remaining, -1)                         # [G,g]
        onehot = jax.nn.one_hot(idx, e, dtype=F32)
        pos = jnp.cumsum(onehot, 1) - 1.0 + counts
        counts = counts + jnp.sum(onehot, 1, keepdims=True)
        slot = jnp.take_along_axis(pos, idx[..., None], -1)[..., 0]
        gate = jnp.take_along_axis(remaining, idx[..., None], -1)[..., 0]
        keep = slot < cap
        picks.append((idx, slot.astype(jnp.int32), keep, gate))
        remaining = remaining * (1.0 - onehot)
    denom = jnp.maximum(sum(jnp.where(kp, gt, 0.0)
                            for _, _, kp, gt in picks), 1e-9)
    # which token fills each expert slot [G,E,cap]; g, a zero row, where
    # none does.  A dropped choice writes to the spare slot ``cap``.
    gi = jnp.arange(G)[:, None]
    owner = jnp.full((G, e, cap + 1), g, jnp.int32)
    tok = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32), (G, g))
    for idx, slot, keep, _ in picks:
        owner = owner.at[gi, idx, jnp.where(keep, slot, cap)].set(tok)
    xpad = jnp.concatenate([xg, jnp.zeros((G, 1, d), xg.dtype)], 1)
    buf = jax.vmap(lambda x, o: x[o])(xpad, owner[:, :, :cap])
    hid = jnp.einsum("gecd,edf->gecf", lowp_round(buf, lowp),
                     lowp_round(p["moe/wi"], lowp), precision=HI)
    gat = jnp.einsum("gecd,edf->gecf", lowp_round(buf, lowp),
                     lowp_round(p["moe/wg"], lowp), precision=HI)
    out = jnp.einsum("gecf,efd->gecd", lowp_round(jax.nn.silu(gat) * hid, lowp),
                     lowp_round(p["moe/wo"], lowp), precision=HI)
    y = jnp.zeros_like(xg)
    for idx, slot, keep, gate in picks:
        got = out[gi, idx, jnp.minimum(slot, cap - 1)]          # [G,g,D]
        y = y + jnp.where(keep, gate / denom, 0.0)[..., None] * got
    return y


def moe(p, u, cfg, decode_from, lowp):
    """u [B,T,D].  Positions before ``decode_from`` were dispatched as one
    flat batch in groups of ``moe_group_size`` tokens (training, prefill);
    each later position was its own decode step, one group of B tokens."""
    b, t, d = u.shape
    cut = t if decode_from is None else decode_from
    parts = []
    if cut:
        flat = u[:, :cut].reshape(b * cut, d)
        n = flat.shape[0]
        g = min(cfg["moe_group_size"], n)
        n_pad = -(-n // g) * g
        flat = jnp.pad(flat, ((0, n_pad - n), (0, 0)))
        y = moe_groups(p, flat.reshape(n_pad // g, g, d), cfg, lowp)
        parts.append(y.reshape(n_pad, d)[:n].reshape(b, cut, d))
    if cut < t:
        steps = u[:, cut:].swapaxes(0, 1)                       # [T-cut,B,D]
        g = min(cfg["moe_group_size"], b)
        assert b % g == 0
        y = moe_groups(p, steps.reshape(-1, g, d), cfg, lowp)
        parts.append(y.reshape(t - cut, b, d).swapaxes(0, 1))
    return jnp.concatenate(parts, 1) if len(parts) > 1 else parts[0]


def layer(p, h, cfg, decode_from=None, lowp=None):
    """One pre-norm block over h [B,T,D]."""
    m = dims(cfg)
    b, t, _ = h.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    u = rmsnorm(h, p["norm/scale"], eps)
    q = mm(u, p["attn/wq/w"], lowp).reshape(b, t, m["h"], m["hd"])
    k = mm(u, p["attn/wk/w"], lowp).reshape(b, t, m["kv"], m["hd"])
    v = mm(u, p["attn/wv/w"], lowp).reshape(b, t, m["kv"], m["hd"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    o = jax.lax.map(lambda qkv: causal_attention(*qkv), (q, k, v))
    h = h + mm(o.reshape(b, t, -1), p["attn/wo/w"], lowp)
    u = rmsnorm(h, p["mlp_norm/scale"], eps)
    if m["e"]:
        y = moe(p, u, cfg, decode_from, lowp)
    else:
        y = mm(jax.nn.silu(mm(u, p["mlp/wg/w"], lowp))
               * mm(u, p["mlp/wi/w"], lowp), p["mlp/wo/w"], lowp)
    return h + y


# --------------------------------------------------------------------------
# block floating point on the branch's matmul operands (straight-through)
# --------------------------------------------------------------------------

def _bfp(x, group: int, ebits: int, mbits: int):
    """Square ``group`` x ``group`` tiles of the 2-D view share the exponent
    floor(log2(max |x|)), clipped to ``ebits`` signed bits; each element
    keeps a sign and ``mbits`` bits: round(x / 2**(e - mbits + 1))."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    m, n = x2.shape
    mp, np_ = -(-m // group) * group, -(-n // group) * group
    xp = jnp.pad(x2, ((0, mp - m), (0, np_ - n)))
    xg = xp.reshape(mp // group, group, np_ // group, group)
    amax = jnp.max(jnp.abs(xg), axis=(1, 3), keepdims=True)
    _, ex = jnp.frexp(amax)
    ex = jnp.where(amax > 0, ex - 1, -127)
    ex = jnp.clip(ex, -(2 ** (ebits - 1)), 2 ** (ebits - 1) - 1)
    scale = jnp.exp2((ex - (mbits - 1)).astype(F32))
    lim = 2 ** mbits - 1
    q = jnp.clip(jnp.round(xg / scale), -lim, lim) * scale
    return q.reshape(mp, np_)[:m, :n].reshape(shape)


bfp = jax.custom_vjp(_bfp, nondiff_argnums=(1, 2, 3))
bfp.defvjp(lambda x, *a: (_bfp(x, *a), None), lambda *a: (a[-1],))


# --------------------------------------------------------------------------
# the duplex branch and the loss
# --------------------------------------------------------------------------

def pool(x, r):
    b, t, d = x.shape
    return x.reshape(b, t // r, r, d).mean(2)


def branch_correction(bp, emb, taps, cfg, lowp=None):
    """emb [B,T,D], taps [n_blocks,B,T/r,D] (pooled) -> correction [B,T,D]."""
    x = cfg["duplex"]
    r, grp = x["pool_factor"], x["bfp_group"]
    q = functools.partial(bfp, group=grp, ebits=x["bfp_ebits"],
                          mbits=x["bfp_mbits"])

    def dense(a, w):
        return mm(q(a), q(w), lowp)

    heads, db = x["branch_heads"], x["d_branch"]
    hd = max(db // heads, 8)

    def f1(p, u):
        b, t, _ = u.shape
        pos = jnp.arange(t)
        qh = rope(dense(u, p["wq"]).reshape(b, t, heads, hd), pos, 1e4)
        kh = rope(dense(u, p["wk"]).reshape(b, t, heads, hd), pos, 1e4)
        vh = dense(u, p["wv"]).reshape(b, t, heads, hd)
        o = jax.vmap(causal_attention)(qh, kh, vh)
        return dense(o.reshape(b, t, heads * hd), p["wo"])

    def f2(p, u):
        return dense(jax.nn.silu(dense(u, p["wg"])) * dense(u, p["wi"]),
                     p["wo2"])

    pooled = pool(emb, r)
    x1 = dense(pooled, bp["in_proj1/w"])
    x2 = dense(pooled, bp["in_proj2/w"])
    for i in range(x["n_blocks"]):
        blk = {n: bp[f"blocks/f1/attn/{n}/w"][i] for n in ("wq", "wk", "wv", "wo")}
        mlp = {"wi": bp["blocks/f2/mlp/wi/w"][i], "wg": bp["blocks/f2/mlp/wg/w"][i],
               "wo2": bp["blocks/f2/mlp/wo/w"][i]}
        x2 = x2 + dense(taps[i], bp["tap_proj/w"][i])
        y2 = x2 + f1(blk, x1)
        y1 = x1 + f2(mlp, y2)
        x1, x2 = y1, y2
    corr = dense(jnp.concatenate([x1, x2], -1), bp["out_proj/w"])
    # token t takes the pooled segment t // r - 1: only complete, past ones
    t = emb.shape[1]
    seg = jnp.arange(t) // r
    up = corr[:, jnp.clip(seg - 1, 0, corr.shape[1] - 1)]
    return jnp.where((seg >= 1)[None, :, None], up, 0.0)


def lm_loss(hidden, labels, table, z_loss, lowp=None):
    """Mean over tokens of lse - logit[label] + z_loss * lse**2, in chunks
    of tokens so that the logits never exist whole."""
    d = hidden.shape[-1]
    c = math.gcd(LOSS_CHUNK, labels.size)
    hs = hidden.reshape(-1, c, d)
    ls = labels.reshape(-1, c)

    @jax.checkpoint
    def chunk(args):
        hc, lc = args
        logits = mm(hc, table.T, lowp)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, lc[:, None], -1)[:, 0]
        return jnp.sum(lse - ll + z_loss * lse * lse)

    return jnp.sum(jax.lax.map(chunk, (hs, ls))) / labels.size


# --------------------------------------------------------------------------
# the model, drawn layer by layer from the seed
# --------------------------------------------------------------------------

class Reference:
    """``stored`` is the dtype the program keeps the backbone in; each
    weight is rounded to it, then computed with in float32."""

    def __init__(self, cfg: dict, seed: int, stored=jnp.float32,
                 lowp: str | None = None):
        self.cfg, self.lowp = cfg, lowp
        self.key = weights.seed_key(seed)
        self.stored = stored
        self.shapes = backbone_shapes(cfg)
        self.m = dims(cfg)
        init = cfg["init"]
        pre = "stack/sub0/"

        # the key is an argument, not a constant: one program for every seed
        def draw_layer(key, i):
            return {path[len(pre):]: weights.layer_slice(
                        key, "backbone", path, shp, init, i
                    ).astype(stored).astype(F32)
                    for path, shp in self.shapes.items()
                    if path.startswith(pre)}

        self._draw_layer = functools.partial(jax.jit(draw_layer), self.key)
        self._layer = jax.jit(
            lambda p, h, decode_from, lowp: layer(p, h, cfg, decode_from, lowp),
            static_argnums=(2, 3))
        table = weights.leaf(self.key, "backbone", "embed/table",
                             self.shapes["embed/table"], init)
        self.table = jax.jit(lambda t: t[:self.m["vocab"]].astype(stored)
                             .astype(F32))(table)
        self.final_scale = weights.leaf(self.key, "backbone",
                                        "final_norm/scale", (self.m["d"],),
                                        init).astype(stored).astype(F32)

    def forward(self, batches, decode_from=None, taps=None):
        """tokens [B,T] of each batch -> (emb, final hidden, pooled taps or
        None) of each.  Layer by layer: each layer's weights are drawn
        once and applied to every batch."""
        hs = [self.table[jnp.asarray(t)] for t in batches]
        embs, tapped = list(hs), [[] for _ in batches]
        want = set() if taps is None else set(int(i) for i in taps)
        r = self.cfg["duplex"]["pool_factor"] if taps is not None else 1
        for i in range(self.m["layers"]):
            p = self._draw_layer(i)
            for j, h in enumerate(hs):
                hs[j] = self._layer(p, h, decode_from, self.lowp)
                if i in want:
                    tapped[j].append(pool(hs[j], r))
            del p
        eps = self.cfg["rms_norm_eps"]
        return [(e, rmsnorm(h, self.final_scale, eps),
                 jnp.stack(t) if taps is not None else None)
                for e, h, t in zip(embs, hs, tapped)]

    def draw_branch(self):
        init = self.cfg["init"]
        return {path: weights.leaf(self.key, "branch", path, shp, init)
                for path, shp in branch_shapes(self.cfg).items()}

    # ---- training: the first steps of the duplex regime -----------------

    def train(self, batches, steps: int) -> dict:
        """Run ``steps`` SGD steps of the branch on ``batches``.

        Returns the losses, the per-leaf norms of the first gradient as the
        optimizer gets it (after clipping) and of the raw first gradient,
        and the per-leaf norms of the branch's change after ``steps``."""
        cfg, x = self.cfg, self.cfg["duplex"]
        idx = tap_indices(self.m["layers"], x["n_blocks"])
        opt = x["sgd"]

        @jax.jit
        def loss_grad(bp, emb, taps, hidden, labels, table):
            def loss(bp):
                corr = branch_correction(bp, emb, taps, cfg, self.lowp)
                return lm_loss(hidden + corr, labels, table, x["z_loss"],
                               self.lowp)
            return jax.value_and_grad(loss)(bp)

        @jax.jit
        def update(bp, mu, g):
            norm = jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))
            s = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-12))
            g = {k: v * s for k, v in g.items()}
            mu = {k: opt["momentum"] * mu[k] + g[k] for k in g}
            bp = {k: bp[k] - x["lr"] * (mu[k] + opt["weight_decay"] * bp[k])
                  for k in bp}
            return bp, mu, g

        with jax.default_matmul_precision("highest"):
            # the backbone is frozen: its passes over all the steps' batches
            # do not depend on the branch, so they run first, together
            fwd = self.forward([b["tokens"] for b in batches[:steps]],
                               taps=idx)
            bp0 = self.draw_branch()
            bp, mu = bp0, {k: jnp.zeros_like(v) for k, v in bp0.items()}
            losses = []
            for s in range(steps):
                emb, hidden, taps = fwd[s]
                fwd[s] = None
                lval, g = loss_grad(bp, emb, taps, hidden,
                                    jnp.asarray(batches[s]["labels"]),
                                    self.table)
                del emb, hidden, taps
                bp, mu, gc = update(bp, mu, g)
                losses.append(float(lval))
                if s == 0:
                    grad = {k: float(jnp.linalg.norm(v)) for k, v in gc.items()}
                    raw = {k: float(jnp.linalg.norm(v)) for k, v in g.items()}
            change = {k: float(jnp.linalg.norm(bp[k] - bp0[k])) for k in bp}
        return {"losses": losses, "grad": grad, "raw_grad": raw,
                "change": change}

    # ---- serving: the final hidden state at every served position ------

    def served_hidden(self, tokens, prompt_len: int):
        """tokens [B, prompt + gen]: prompts and their served tokens.
        Returns the final hidden state [B, gen, D] of positions
        prompt-1 ... prompt+gen-2, which predict the served tokens."""
        seq = jnp.asarray(tokens)
        with jax.default_matmul_precision("highest"):
            (_, hidden, _), = self.forward([seq[:, :-1]],
                                           decode_from=prompt_len)
        return hidden[:, prompt_len - 1:]

    def logit_stats(self, hidden, picks):
        """(max logit, logit of ``picks``, argmax), each [B, gen]."""
        return _logit_stats(hidden, jnp.asarray(picks), self.table,
                            self.lowp)


@functools.partial(jax.jit, static_argnums=(3,))
def _logit_stats(hid, picks, table, lowp):
    def one(args):
        h, s = args
        logits = mm(h, table.T, lowp)
        return (jnp.max(logits, -1),
                jnp.take_along_axis(logits, s[:, None], -1)[:, 0],
                jnp.argmax(logits, -1))
    return jax.lax.map(one, (hid, picks))
