"""Plain reference for the training cells.

The model (pre-norm decoder: RMSNorm, multi-head attention with rotary or
sinusoidal positions, SwiGLU feed-forward, tied or separate unembedding),
its next-token loss and gradients, the traffic's token stream, and MARINA's
gradient-carry round on the seeded block wire, written in straightforward
``jax.numpy``. It imports nothing of the program under test: what it shares
with the program is the input format (the weight tree and the wire layout)
and the specification of the randomness (JAX keys and the murmur3 counter
hash that the wire format defines).

Float32 runs at ``Precision.HIGHEST``. ``dtype=bfloat16`` is the control: the
same reference computed one precision lower.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the reference needs, read from a configuration file."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    num_hidden_layers: int
    rms_norm_eps: float
    rope_theta: float
    position_embedding: str   # "rope" | "sinusoidal"
    qkv_bias: bool
    tie_word_embeddings: bool
    prefix_len: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        heads = cfg["num_attention_heads"]
        return cls(
            hidden_size=cfg["hidden_size"],
            num_attention_heads=heads,
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            intermediate_size=cfg["intermediate_size"],
            vocab_size=cfg["vocab_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            rms_norm_eps=cfg["rms_norm_eps"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            position_embedding=cfg["position_embedding"],
            qkv_bias=bool(cfg["qkv_bias"]),
            tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
            prefix_len=int(cfg.get("prefix_len", 0)),
        )


# ---------------------------------------------------------------------------
# weights: the program's input format, made from the seed
# ---------------------------------------------------------------------------


def weight_shapes(m: Model) -> dict:
    """The weight tree as the trainer takes it: one stacked dense segment."""
    L, d, H, KV, hd, F, V = (
        m.num_hidden_layers, m.hidden_size, m.num_attention_heads,
        m.num_key_value_heads, m.head_dim, m.intermediate_size, m.vocab_size,
    )
    mixer = {
        "wq": (L, d, H * hd), "wk": (L, d, KV * hd), "wv": (L, d, KV * hd),
        "wo": (L, H * hd, d),
    }
    if m.qkv_bias:
        mixer.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    layer = {
        "ln1": (L, d), "ln2": (L, d), "mixer": mixer,
        "ff": {"w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d)},
    }
    tree = {"embed": (V, d), "final_norm": (d,), "segments": [[layer]]}
    if not m.tie_word_embeddings:
        tree["lm_head"] = (V, d)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), tree,
        is_leaf=lambda s: isinstance(s, tuple),
    )


def _init_scale(path: str, shape: tuple, m: Model) -> tuple[float, float]:
    """(mean, std) of a leaf: fan-in scaled matrices, unit-ish norms and
    small biases, so every path of the layer carries signal from step one."""
    if path.endswith(("ln1", "ln2", "final_norm")):
        return 1.0, 0.02
    if path.endswith(("bq", "bk", "bv")):
        return 0.0, 0.02
    if path.endswith(("embed", "lm_head")):
        return 0.0, m.hidden_size ** -0.5
    return 0.0, shape[-2] ** -0.5


def make_weights(m: Model, seed: int) -> dict:
    """Seeded weights on the device, in one jitted call, in float32."""
    shapes = weight_shapes(m)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, sd) in enumerate(paths):
            name = jax.tree_util.keystr(path)
            mean, std = _init_scale(name.strip("[]'\""), sd.shape, m)
            leaf = jax.random.normal(jax.random.fold_in(key, i), sd.shape) * std
            leaves.append(leaf + mean)
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the traffic's inputs: per-worker token streams and the stub prefix
# ---------------------------------------------------------------------------


def _worker_tokens(key, worker, n: int, vocab: int, seq: int, batch: int):
    """(batch, seq) tokens of one worker: a hashed grammar (token·31 + 7)
    mixed 70:30 with draws around a worker-specific centre of the vocabulary,
    so that workers' gradients differ."""
    V = vocab
    _, k_start, k_noise = jax.random.split(key, 3)
    offset = ((worker.astype(jnp.float32) + 0.5) / n - 0.5) * V
    center = V / 2.0 + offset
    width = V * (1.0 - 0.7) + 1.0
    start = jax.random.randint(k_start, (batch,), 0, V)

    def step(tok, k):
        k1, k2 = jax.random.split(k)
        nxt = (tok * 31 + 7) % V
        noise = jax.random.normal(k1, tok.shape) * width * 0.1
        biased = jnp.clip(center + noise, 0, V - 1).astype(jnp.int32)
        use_hash = jax.random.bernoulli(k2, 0.7, tok.shape)
        out = jnp.where(use_hash, nxt, biased)
        return out, out

    _, rest = jax.lax.scan(step, start, jax.random.split(k_noise, seq - 1))
    return jnp.concatenate([start[None, :], rest], axis=0).T


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def round_inputs(step, seed: int, n: int, vocab: int, seq: int, batch: int,
                 prefix_len: int, d: int):
    """Tokens (n, batch, seq) and prefix (n, batch, P, d) or None of a round."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    tokens = jax.vmap(
        lambda w: _worker_tokens(
            jax.random.fold_in(base, w), w, n, vocab, seq, batch
        )
    )(jnp.arange(n))
    prefix = None
    if prefix_len:
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 7), step)
        prefix = jax.random.normal(k, (n, batch, prefix_len, d)) * 0.02
    return tokens, prefix


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _sinusoids(pos, d):
    half = d // 2
    freqs = 1.0 / (10_000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def loss(w: dict, tokens, prefix, m: Model, dtype=jnp.float32):
    """Mean next-token cross-entropy over token positions (prefix excluded)
    of rows ``tokens`` (b, S) with optional prefix embeddings (b, P, d)."""
    prec = HIGHEST if dtype == jnp.float32 else None
    mm = functools.partial(jnp.matmul, precision=prec)
    es = functools.partial(jnp.einsum, precision=prec)
    w = jax.tree.map(lambda t: t.astype(dtype), w)
    H, KV, hd = m.num_attention_heads, m.num_key_value_heads, m.head_dim
    x = w["embed"][tokens]
    P = 0
    if prefix is not None:
        P = prefix.shape[1]
        x = jnp.concatenate([prefix.astype(dtype), x], axis=1)
    b, T, _ = x.shape
    pos = jnp.arange(T)
    if m.position_embedding == "sinusoidal":
        x = x + _sinusoids(pos, m.hidden_size).astype(dtype)
    causal = jnp.tril(jnp.ones((T, T), bool))
    layers = w["segments"][0][0]
    for li in range(m.num_hidden_layers):
        p = jax.tree.map(lambda t: t[li], layers)
        a = p["mixer"]
        h = _rmsnorm(x, p["ln1"], m.rms_norm_eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if m.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = q.reshape(b, T, H, hd)
        k = k.reshape(b, T, KV, hd)
        v = v.reshape(b, T, KV, hd)
        if m.position_embedding == "rope":
            q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
        if KV != H:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        s = es("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        att = es("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + mm(att.reshape(b, T, H * hd), a["wo"])
        h = _rmsnorm(x, p["ln2"], m.rms_norm_eps)
        f = p["ff"]
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                   f["w_down"])
    x = _rmsnorm(x, w["final_norm"], m.rms_norm_eps)
    table = w["embed"] if m.tie_word_embeddings else w["lm_head"]
    logits = es("btd,vd->btv", x[:, P:-1], table)
    if dtype == jnp.float32:
        logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _grad_rows(w, tokens, prefix, m: Model, dtype):
    g = jax.grad(loss)(w, tokens, prefix, m, dtype)
    return jax.tree.map(lambda t: t.astype(jnp.float32), g)


@functools.partial(jax.jit, static_argnums=(1,))
def _cast(tree, dtype):
    return jax.tree.map(lambda t: t.astype(dtype), tree)


@jax.jit
def _axpy(a, x, y):
    return jax.tree.map(lambda u, v: a * u + v, x, y)


def worker_grad(w, tokens, prefix, m: Model, dtype, rows: int):
    """∇ of the mean loss over a worker's (b, S) rows, ``rows`` at a time,
    summed in float32 and kept in ``dtype``."""
    b = tokens.shape[0]
    acc = None
    for r0 in range(0, b, rows):
        pre = None if prefix is None else prefix[r0:r0 + rows]
        g = _grad_rows(w, tokens[r0:r0 + rows], pre, m, dtype)
        frac = jnp.float32(min(rows, b - r0) / b)
        acc = jax.tree.map(lambda u: frac * u, g) if acc is None else _axpy(frac, g, acc)
    return _cast(acc, dtype)


# ---------------------------------------------------------------------------
# the wire: seeded block compression of one worker's flat vector
# ---------------------------------------------------------------------------


def murmur(seed, ctr):
    """The wire's counter hash: murmur3's 32-bit finalizer of ctr·φ + seed."""
    x = ctr.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _flat(tree, nblk: int, block: int):
    flat = jnp.concatenate([jnp.ravel(t) for t in jax.tree.leaves(tree)])
    return jnp.pad(flat, (0, nblk * block - flat.size)).reshape(nblk, block)


def _unflat(x2d, like):
    leaves, treedef = jax.tree.flatten(like)
    flat = x2d.reshape(-1)
    out, off = [], 0
    for leaf in leaves:
        out.append(flat[off:off + leaf.size].reshape(leaf.shape))
        off += leaf.size
    return jax.tree.unflatten(treedef, out)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def decompressed(diff, seed, sampler: str, block: int, level: int):
    """Q(diff) as the server decodes it: seeded block QSGD with ``level``
    levels, or seeded RandK with ``level`` coordinates per block."""
    d = sum(t.size for t in jax.tree.leaves(diff))
    nblk = -(-d // block)
    x = _flat(diff, nblk, block)
    if sampler == "qsgd":
        norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
        ctr = (jnp.arange(block, dtype=jnp.uint32)[None, :]
               + (jnp.arange(nblk, dtype=jnp.uint32) * block)[:, None])
        u = (murmur(seed, ctr) >> 8).astype(jnp.float32) * jnp.float32(2.0**-24)
        lv = jnp.floor(level * jnp.abs(x) / jnp.where(norm > 0, norm, 1.0) + u)
        out = jnp.sign(x) * lv * (norm / level)
    elif sampler == "randk":
        ctr = (jnp.arange(level, dtype=jnp.uint32)[None, :]
               + (jnp.arange(nblk, dtype=jnp.uint32) * level)[:, None])
        off = (murmur(seed, ctr) & jnp.uint32(block - 1)).astype(jnp.int32)
        vals = jnp.take_along_axis(x, off, axis=1) * (block / level)
        rows = jnp.broadcast_to(jnp.arange(nblk)[:, None], off.shape)
        out = jnp.zeros_like(x).at[rows, off].add(vals)
    else:
        raise ValueError(f"unknown sampler {sampler!r}")
    return _unflat(out, diff)


def wire_bits(sampler: str, d: int, block: int, level: int) -> float:
    """Bits one worker uploads in a compressed round: per-block f32 norm and
    a 4-bit level per coordinate (QSGD, level ≤ 7), or a uint32 seed and
    ``level`` f32 values per block (RandK)."""
    nblk = -(-d // block)
    if sampler == "qsgd":
        assert level <= 7, "levels above 7 do not fit a 4-bit nibble"
        return 32.0 * nblk + 4.0 * nblk * block
    return 32.0 + 32.0 * nblk * level


def sync_probability(sampler: str, d: int, block: int, level: int) -> float:
    """The chance of a dense round: ζ/d coordinates sent (RandK, Cor. 2.1),
    or equal expected bits in dense and compressed rounds (QSGD)."""
    nblk = -(-d // block)
    if sampler == "qsgd":
        p = wire_bits(sampler, d, block, level) / (32.0 * d)
    else:
        p = min(d, nblk * level) / d
    return min(1.0, max(p, 1e-6))


# ---------------------------------------------------------------------------
# MARINA's gradient-carry round
# ---------------------------------------------------------------------------


@jax.jit
def _mean(trees):
    return jax.tree.map(lambda *t: sum(t) / len(t), *trees)


@jax.jit
def _sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


@functools.partial(jax.jit, static_argnums=(2,))
def _step_x(x, g, gamma: float):
    return jax.tree.map(lambda xi, gi: (-gamma) * gi + xi, x, g)


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
                      for leaf in jax.tree.leaves(tree)])


def leaf_norms(tree) -> np.ndarray:
    """ℓ2 norm of every leaf, in flatten order, as float64 on the host."""
    return np.asarray(_leaf_norms(tree), np.float64)


@dataclasses.dataclass
class Marina:
    """Lookahead gradient-carry MARINA (one backprop a round): the state is
    (x^{k+1}, g^k, h_i = ∇f_i at x^k); round k evaluates ∇f_i(x^{k+1}; b_k),
    flips c_k ~ Be(p) from the round key, and sets g^{k+1} to the workers'
    mean (c_k = 1) or g^k + mean_i Q_i(∇f_i − h_i) (c_k = 0), then steps x.

    With ``dtype=bfloat16`` the whole reference runs one precision lower:
    weights, activations, gradients and the state (x, g, h) in bfloat16.
    ``fault`` plants a fault: "half_batch" leaves out half of the global
    batch and takes the mean over the rest."""

    m: Model
    sampler: str
    block: int
    level: int
    n: int
    batch: int
    seq: int
    gamma: float
    stream_seed: int
    dtype: object = jnp.float32
    rows: int = 8
    fault: str | None = None

    def __post_init__(self):
        d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(weight_shapes(self.m)))
        self.d = d
        self.p = sync_probability(self.sampler, d, self.block, self.level)

    def _grads(self, x, step):
        tokens, prefix = round_inputs(
            step, self.stream_seed, self.n, self.m.vocab_size, self.seq, self.batch,
            self.m.prefix_len, self.m.hidden_size,
        )
        kept = self.n
        if self.fault == "half_batch" and self.batch == 1:
            kept = self.n // 2
        b = self.batch // 2 if self.fault == "half_batch" and self.batch > 1 else self.batch
        out = []
        for i in range(self.n):
            if i >= kept:
                out.append(jax.tree.map(jnp.zeros_like, x))
                continue
            pre = None if prefix is None else prefix[i, :b]
            out.append(worker_grad(x, tokens[i, :b], pre, self.m, self.dtype, self.rows))
        return out, kept

    def init(self, x0):
        x0 = _cast(x0, self.dtype)
        h, kept = self._grads(x0, 0)
        g = _mean(h[:kept])
        return {"x": _step_x(x0, g, self.gamma), "g": g, "h": h}

    def coin(self, step) -> bool:
        k_bern, _ = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(self.stream_seed), step))
        return bool(jax.random.bernoulli(k_bern, self.p))

    def round(self, state, step):
        grads, kept = self._grads(state["x"], step)
        key = jax.random.fold_in(jax.random.PRNGKey(self.stream_seed), step)
        _, k_q = jax.random.split(key)
        if self.coin(step):
            g = _mean(grads[:kept])
        else:
            seeds = jax.vmap(lambda k: jax.random.bits(k, dtype=jnp.uint32))(
                jax.random.split(k_q, self.n))
            q = [_cast(decompressed(_sub(grads[i], state["h"][i]), seeds[i],
                                    self.sampler, self.block, self.level),
                       self.dtype)
                 for i in range(kept)]
            g = _cast(_axpy(jnp.float32(1.0), state["g"],
                            _mean(q)), self.dtype)
        return {"x": _step_x(state["x"], g, self.gamma), "g": g, "h": grads}
