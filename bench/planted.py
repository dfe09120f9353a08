"""Traffic rows and planted CoTM weights, both made from ``--seed``.

The two row generators are copies of the repository's synthetic data
(procedural MNIST-like digit glyphs, and the Table 5 Boolean prototype
datasets), kept here so that the benchmark's inputs cannot change under
a later PR.  Rows are booleanized as the paper does: one bit per feature
(``x > 0.5``) followed by its negation, so K = 2 * features.

A *planted* CoTM stands in for a trained one.  Each clause belongs to one
class and includes a few literals that are frequent in that class's rows
and rare in the others' (drawn from per-class literal frequencies of the
same generator), so clauses fire on the traffic and the predictions vary
across classes.  Its TA states and integer weights are made on the device
in one jitted call.  No training loop runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_FONT = {
    0: [".###.", "#...#", "#..##", "#.#.#", "##..#", "#...#", ".###."],
    1: ["..#..", ".##..", "..#..", "..#..", "..#..", "..#..", ".###."],
    2: [".###.", "#...#", "....#", "...#.", "..#..", ".#...", "#####"],
    3: [".###.", "#...#", "....#", "..##.", "....#", "#...#", ".###."],
    4: ["...#.", "..##.", ".#.#.", "#..#.", "#####", "...#.", "...#."],
    5: ["#####", "#....", "####.", "....#", "....#", "#...#", ".###."],
    6: ["..##.", ".#...", "#....", "####.", "#...#", "#...#", ".###."],
    7: ["#####", "....#", "...#.", "..#..", ".#...", ".#...", ".#..."],
    8: [".###.", "#...#", "#...#", ".###.", "#...#", "#...#", ".###."],
    9: [".###.", "#...#", "#...#", ".####", "....#", "...#.", ".##.."],
}


def _glyph(digit: int) -> np.ndarray:
    rows = _FONT[digit]
    g = np.array([[c == "#" for c in r] for r in rows], dtype=np.float32)
    return np.kron(g, np.ones((3, 3), np.float32))


def digits(n: int, *, seed: int, noise: float = 0.03,
           jitter: int = 3) -> tuple[np.ndarray, np.ndarray]:
    """(images (n, 784) float32 in [0, 1], labels (n,) int32): 5x7 font
    glyphs upscaled x3, placed with jitter on a noisy 28x28 canvas."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    imgs = np.zeros((n, 28, 28), np.float32)
    glyphs = {d: _glyph(d) for d in range(10)}
    for i, d in enumerate(labels):
        g = glyphs[int(d)]
        h, w = g.shape
        dy = rng.integers(0, 28 - h - jitter) + rng.integers(0, jitter + 1)
        dx = rng.integers(0, 28 - w - jitter) + rng.integers(0, jitter + 1)
        canvas = rng.uniform(0.0, 0.15, (28, 28)).astype(np.float32)
        patch = np.where(g > 0, rng.uniform(0.6, 1.0, g.shape),
                         canvas[dy:dy + h, dx:dx + w])
        canvas[dy:dy + h, dx:dx + w] = patch
        flip = rng.random((28, 28)) < noise
        imgs[i] = np.where(flip, 1.0 - canvas, canvas)
    return imgs.reshape(n, 784), labels


def prototype(n: int, *, n_classes: int, n_features: int, seed: int,
              proto_seed: int, protos_per_class: int = 2,
              flip: float = 0.08) -> tuple[np.ndarray, np.ndarray]:
    """Boolean prototype rows: pick one of the class's random prototypes
    (fixed by ``proto_seed``) and flip each bit with probability ``flip``."""
    proto_rng = np.random.default_rng(proto_seed)
    rng = np.random.default_rng(seed)
    protos = proto_rng.random((n_classes, protos_per_class, n_features)) < 0.5
    labels = rng.integers(0, n_classes, size=n).astype(np.int32)
    which = rng.integers(0, protos_per_class, size=n)
    x = protos[labels, which].astype(np.float32)
    mask = rng.random((n, n_features)) < flip
    return np.where(mask, 1.0 - x, x), labels


def booleanize(x: np.ndarray) -> np.ndarray:
    """(n, F) features in [0, 1] -> (n, 2F) int8 literals [x > 0.5, not]."""
    bits = x > 0.5
    return np.concatenate([bits, ~bits], axis=1).astype(np.int8)


#: Streams derived from one run seed, by index.
FREQ_ROWS, WEIGHTS, POOL, ARRIVALS, SAMPLE = range(5)


def derive(seed: int, stream: int) -> int:
    """A 63-bit seed for one stream of a run, independent of the others;
    ``seed`` may be any non-negative integer."""
    state = np.random.SeedSequence(int(seed)).generate_state(8, np.uint64)
    return int(state[stream]) >> 1


def rows(cfg: dict, n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` literal rows (n, K) int8 and their labels for configuration
    ``cfg``, from the generator its ``data`` key names."""
    data = cfg["data"]
    s_rows, s_proto = derive(seed, 0), derive(seed, 1)
    if data["generator"] == "digits":
        x, y = digits(n, seed=s_rows, noise=data["noise"],
                      jitter=data["jitter"])
    elif data["generator"] == "prototype":
        x, y = prototype(n, n_classes=cfg["n_classes"],
                         n_features=cfg["n_literals"] // 2, seed=s_rows,
                         proto_seed=s_proto,
                         protos_per_class=data["protos_per_class"],
                         flip=data["flip"])
    else:
        raise ValueError(f"unknown data generator {data['generator']!r}")
    lits = booleanize(x)
    if lits.shape[1] != cfg["n_literals"]:
        raise ValueError(f"generator {data['generator']!r} gives "
                         f"{lits.shape[1]} literals, the configuration "
                         f"states {cfg['n_literals']}")
    return lits, y


def literal_frequencies(lits: np.ndarray, labels: np.ndarray,
                        n_classes: int) -> np.ndarray:
    """(m, K) share of each class's rows in which each literal is 1."""
    return np.stack([lits[labels == c].mean(axis=0) if np.any(labels == c)
                     else np.full(lits.shape[1], 0.5)
                     for c in range(n_classes)]).astype(np.float32)


@functools.partial(jax.jit, static_argnames=(
    "n_clauses", "n_states", "k_min", "k_max", "w_pos", "w_neg"))
def _plant(key, freq, *, n_clauses, n_states, k_min, k_max, w_pos, w_neg):
    m, K = freq.shape
    k_inc, k_cnt, k_ta_in, k_ta_ex, k_wp, k_wn = jax.random.split(key, 6)
    cls = jnp.arange(n_clauses) % m
    eps = 1e-3
    # A literal is a good pick for class c when it is 1 in c's rows and 0
    # in the others': log p_c + log(1 - mean p_other).
    other = (freq.sum(0, keepdims=True) - freq) / max(m - 1, 1)
    score = jnp.log(freq + eps) + jnp.log(1.0 - other + eps)       # (m, K)
    gumbel = jax.random.gumbel(k_inc, (n_clauses, K))
    noisy = score[cls] + gumbel                                      # (n, K)
    k = jax.random.randint(k_cnt, (n_clauses,), k_min, k_max + 1)
    top = jax.lax.top_k(noisy, k_max)[0]                             # (n, k_max)
    kth = jnp.take_along_axis(top, (k - 1)[:, None], axis=1)
    include = noisy >= kth                                           # (n, K)
    ta_in = n_states + jax.random.randint(k_ta_in, (n_clauses, K), 1,
                                          n_states + 1)
    ta_ex = jax.random.randint(k_ta_ex, (n_clauses, K), 1, n_states + 1)
    ta_state = jnp.where(include, ta_in, ta_ex).T.astype(jnp.int32)  # (K, n)
    own = cls[None, :] == jnp.arange(m)[:, None]                     # (m, n)
    w = jnp.where(own,
                  jax.random.randint(k_wp, (m, n_clauses), 1, w_pos + 1),
                  -jax.random.randint(k_wn, (m, n_clauses), 0, w_neg + 1))
    # Pin both extremes so the unipolar shift and the largest weight, and
    # with them every class cell's programming target, are the same for
    # every seed.
    w = w.at[cls[0], 0].set(w_pos).at[(cls[0] + 1) % m, 0].set(-w_neg)
    return ta_state, w.astype(jnp.int32)


def planted(cfg: dict, seed: int):
    """-> (ta_state (K, n) int32, weights (m, n) int32) as device arrays,
    from one jitted call.  The per-class literal frequencies come from a
    sample of the configuration's own generator."""
    p = cfg["planted"]
    lits, y = rows(cfg, p["frequency_rows"], derive(seed, FREQ_ROWS))
    freq = literal_frequencies(lits, y, cfg["n_classes"])
    key = jax.random.key(derive(seed, WEIGHTS) % (2 ** 31))
    return _plant(key, freq, n_clauses=cfg["n_clauses"],
                  n_states=cfg["n_states"], k_min=p["includes_min"],
                  k_max=p["includes_max"], w_pos=p["weight_pos_max"],
                  w_neg=p["weight_neg_max"])


def pool(cfg: dict, n: int, seed: int) -> np.ndarray:
    """The run's (n, K) int8 pool of literal rows, which the traffic
    cycles through; drawn apart from the rows the weights were planted
    from."""
    return rows(cfg, n, derive(seed, POOL))[0]
