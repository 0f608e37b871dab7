"""The plain reference and the comparison that decides ``correct``.

The reference is a brute force over the live multiset in numpy: no
code, weights or tables of the program.  Vectors hold integers 0..255
with d=128, so every dot product, squared norm and score
``||x||^2 - 2 q.x`` is an integer below 2**24 and float32 computes it
exactly: the reference's distances are exact, and ties are real ties.

Liveness "as of a dispatch" comes from the proxy's update numbers: an
id is live at update number ``u`` when ``ins_seq < u <= del_seq``.

The control (``control_bf16``) is the same brute force computed in
bfloat16, put in the program's place: the check must find it wrong.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

K = 10


def live_at(ins_seq, del_seq, ids, u):
    """Bool array: ``ids`` (any shape, -1 = none) live at update ``u``
    (broadcast over ids' leading axis)."""
    ok = ids >= 0
    i = np.where(ok, ids, 0)
    u = np.asarray(u)[(...,) + (None,) * (ids.ndim - np.ndim(u))]
    return ok & (ins_seq[i] < u) & (del_seq[i] >= u)


def scores(vecs, norms, q):
    """Exact ``||x||^2 - 2 q.x`` of queries ``q`` against every row."""
    return norms[None, :] - 2.0 * (q @ vecs.T)


def truth_kth(vecs, norms, ins_seq, del_seq, q, u, chunk: int = 16):
    """The K-th smallest exact score over the rows live at each query's
    update number (one per query).  Rows live at no query's update
    number are dropped first; query chunks run on a few threads."""
    rows = np.flatnonzero((ins_seq < u.max()) & (del_seq >= u.min()))
    x, xn, ins, dele = vecs[rows], norms[rows], ins_seq[rows], del_seq[rows]
    out = np.empty(len(q), np.float32)

    def one(s):
        sc = scores(x, xn, q[s:s + chunk])
        uc = u[s:s + chunk, None]
        sc[(ins[None, :] >= uc) | (dele[None, :] < uc)] = np.inf
        out[s:s + chunk] = np.partition(sc, K - 1, axis=1)[:, K - 1]

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(one, range(0, len(q), chunk)))
    return out


def recall(vecs, norms, ins_seq, del_seq, q, u, served):
    """Per query: the share of the ``K`` served ids that are live at
    dispatch and no farther than the true K-th neighbour (ties count)."""
    kth = truth_kth(vecs, norms, ins_seq, del_seq, q, u)
    ids = np.where(served >= 0, served, 0)
    d = norms[ids] - 2.0 * np.einsum("qkd,qd->qk", vecs[ids], q)
    good = live_at(ins_seq, del_seq, served, u) & (d <= kth[:, None])
    return good.sum(1) / K


def score_gap(vecs, norms, q, served, served_scores) -> float:
    """The widest gap between a served score and the exact score of the
    id it was served with (ids of -1 are skipped)."""
    ok = served >= 0
    ids = np.where(ok, served, 0)
    exact = norms[ids] - 2.0 * np.einsum("qkd,qd->qk", vecs[ids], q)
    gap = np.abs(np.where(ok, served_scores - exact, 0.0))
    return float(gap.max(initial=0.0))


def readback_misses(vecs, served_first, target) -> int:
    """Read-backs whose first served id is neither the inserted vector
    nor a vector equal to it."""
    ok = served_first >= 0
    same = np.zeros(len(target), bool)
    same[ok] = (served_first[ok] == target[ok]) | np.all(
        vecs[served_first[ok]] == vecs[target[ok]], axis=1)
    return int((~same).sum())


def _i32(a):
    return np.clip(a, -1, np.iinfo(np.int32).max).astype(np.int32)


def control_bf16(vecs, ins_seq, del_seq, q, u, chunk: int = 32):
    """The brute force computed in bfloat16 on the default device: the
    top-``K`` (ids, scores) of each query over the rows live at its
    update number, ties to the lower id."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(vecs, jnp.bfloat16)
    xn = jnp.sum(x * x, axis=1, dtype=jnp.bfloat16)
    ins, dele = jnp.asarray(_i32(ins_seq)), jnp.asarray(_i32(del_seq))
    u = _i32(u)

    @jax.jit
    def top(x, xn, ins, dele, qc, uc):
        g = jnp.dot(qc.astype(jnp.bfloat16), x.T,
                    preferred_element_type=jnp.bfloat16)
        sc = xn[None, :] - jnp.bfloat16(2) * g
        live = (ins[None, :] < uc[:, None]) & (dele[None, :] >= uc[:, None])
        sc = jnp.where(live, sc.astype(jnp.float32), jnp.inf)
        neg, ids = jax.lax.top_k(-sc, K)
        return ids, -neg

    ids, scs = [], []
    for s in range(0, len(q), chunk):
        qc, uc = q[s:s + chunk], u[s:s + chunk]
        pad = chunk - len(qc)
        qc = np.concatenate([qc, np.zeros((pad, q.shape[1]), q.dtype)])
        uc = np.concatenate([uc, np.zeros(pad, uc.dtype)])
        i, sc = top(x, xn, ins, dele, qc, uc)
        ids.append(np.asarray(i)[:chunk - pad])
        scs.append(np.asarray(sc)[:chunk - pad])
    return np.concatenate(ids).astype(np.int64), np.concatenate(scs)
