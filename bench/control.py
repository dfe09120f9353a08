"""The control of the correctness check: the plain reference put in the
program's place and computed one precision step below the configuration's
float32, that is bfloat16: every contraction takes its float32 operands
rounded to bfloat16 and accumulates the exact products in float32, as
one MXU pass does.  The rounding is spelled out, so the control computes
the same numbers on a CPU and on a TPU.  A sound check has to find it not
correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference


def _dot_bf16(a, b):
    """float32 a @ b from bfloat16-rounded operands, in float32."""
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(bf(a), bf(b), precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("shards", "thresh", "v_t"))
def _infer(lits, clause_i, nonempty, class_i, *, shards, thresh, v_t):
    rows_pad, cols = clause_i.shape
    B, K = lits.shape
    drive = jnp.zeros((B, rows_pad), jnp.float32).at[:, :K].set(
        1.0 - lits.astype(jnp.float32))
    fired = jnp.broadcast_to(nonempty, (B, cols))
    i_clause = jnp.zeros((B,), jnp.float32)
    step = rows_pad // shards
    for r in range(shards):
        i_col = _dot_bf16(drive[:, r * step:(r + 1) * step],
                          clause_i[r * step:(r + 1) * step])
        fired = fired & (i_col < thresh)
        i_clause = i_clause + i_col.sum(axis=1)
    k = min(cols, class_i.shape[0])
    drv = jnp.zeros((B, class_i.shape[0]), jnp.float32).at[:, :k].set(
        fired[:, :k].astype(jnp.float32))
    scores = _dot_bf16(drv, class_i)
    return (jnp.argmax(scores, axis=1), v_t * i_clause,
            v_t * scores.sum(axis=1))


def infer(fab: reference.Fabric, literals: np.ndarray, block: int) -> dict:
    """Rows (B, K) -> ``pred`` (B,), float32 ``e_clause`` / ``e_class``
    (B,) joules, in blocks of ``block`` rows (the padded tail is cut)."""
    ci = jnp.asarray(fab.clause_i, jnp.float32)
    ne = jnp.asarray(fab.nonempty)
    wi = jnp.asarray(fab.class_i, jnp.float32)
    out = dict(pred=[], e_clause=[], e_class=[])
    for b0 in range(0, len(literals), block):
        rows = np.ones((block, literals.shape[1]), np.int8)
        part = literals[b0:b0 + block]
        rows[:len(part)] = part
        pred, e_cl, e_cs = _infer(
            jnp.asarray(rows), ci, ne, wi, shards=fab.shards,
            thresh=float(fab.thresh), v_t=float(fab.v_read * fab.t_read))
        for key, v in zip(out, (pred, e_cl, e_cs)):
            out[key].append(np.asarray(v)[:len(part)])
    return {k: np.concatenate(v) for k, v in out.items()}
