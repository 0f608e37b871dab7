"""Product quantization for the posting tiles (FreshDiskANN-style tier).

The quant plane keeps an ``(M, m, C)`` uint8 code array beside the float
posting tiles: search can scan compressed codes with an ADC lookup-table
kernel (``kernels/pq_scan.py``) and exact-rerank only the top
``cfg.rerank_k`` float candidates, cutting phase-2 posting bytes by
``4 * dim / m`` (16x at dim=32, m=8).

Codebooks are **versioned** so a background re-train never invalidates
codes written under an older generation: ``state.pq_codebooks`` holds
``V = cfg.pq_versions`` slots, each posting records the slot its codes
were written under (``pq_posting_slot``), and search builds one lookup
table per live slot.  A re-train writes the new generation into the
*oldest* slot; postings still pinned to that slot are re-encoded inside
the same device program (nothing is ever undecodable), while postings on
other slots upgrade lazily the next time a split/merge/compact rewrites
their tile.  This is the streaming-codebook regime of "Quantization for
Vector Search under Streaming Updates" (PAPERS.md): local refresh from a
fresh sample, never a global rebuild.

Invariant (property-tested in tests/test_pq.py): for every *valid* slot
of every live posting, ``codes[p, :, c] == encode(codebooks[slot[p]],
vectors[p, c])`` — the code plane and the float plane never diverge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops, ref


# ---------------------------------------------------------------------------
# encode / decode / lookup tables (pure functions of one codebook set)
# ---------------------------------------------------------------------------

# rows encoded per step: the (m, rows, ksub) f32 score block stays
# at 256 MiB for m=16, ksub=256 (a whole 3M-row pool at once is 51 GB)
ENCODE_ROWS = 16384


def _encode_rows(codebooks: jax.Array, x: jax.Array) -> jax.Array:
    m, ksub, dsub = codebooks.shape
    n = x.shape[0]
    xs = x.astype(jnp.float32).reshape(n, m, dsub).transpose(1, 0, 2)
    cn = jnp.sum(codebooks * codebooks, axis=-1)            # (m, ksub)
    dots = jnp.einsum("jnd,jkd->jnk", xs, codebooks,
                      precision=ref.HIGHEST)                # (m, N, ksub)
    scores = cn[:, None, :] - 2.0 * dots
    return ref.exact_argmin(scores).astype(jnp.uint8).T    # (N, m)


def encode(codebooks: jax.Array, x: jax.Array) -> jax.Array:
    """Nearest-centroid codes per subspace, ``ENCODE_ROWS`` rows at a
    time.

    codebooks: (m, ksub, dsub) f32; x: (N, d) -> (N, m) uint8.
    """
    n, d = x.shape
    if n <= ENCODE_ROWS:
        return _encode_rows(codebooks, x)
    xb = jnp.pad(x, ((0, -n % ENCODE_ROWS), (0, 0)))
    codes = jax.lax.map(lambda b: _encode_rows(codebooks, b),
                        xb.reshape(-1, ENCODE_ROWS, d))
    return codes.reshape(-1, codebooks.shape[0])[:n]


def encode_all_versions(codebooks_v: jax.Array, x: jax.Array) -> jax.Array:
    """Encode under every codebook slot at once: (V, N, m) uint8.

    Appends target postings pinned to arbitrary slots; encoding under all
    ``V`` (small, static) slots then selecting per job beats a per-job
    codebook gather.
    """
    return jax.vmap(encode, in_axes=(0, None))(codebooks_v, x)


def decode(codebooks: jax.Array, codes: jax.Array) -> jax.Array:
    """codebooks: (m, ksub, dsub); codes: (N, m) -> (N, m*dsub) f32."""
    m, ksub, dsub = codebooks.shape
    n = codes.shape[0]
    sub = codebooks[jnp.arange(m)[None, :], codes.astype(jnp.int32)]
    return sub.reshape(n, m * dsub)


def encode_tiles(codebooks: jax.Array, tiles: jax.Array) -> jax.Array:
    """Encode whole posting tiles: (B, C, d) -> (B, m, C) subspace-major."""
    B, C, d = tiles.shape
    codes = encode(codebooks, tiles.reshape(B * C, d))      # (B*C, m)
    return codes.reshape(B, C, -1).transpose(0, 2, 1)       # (B, m, C)


def lookup_tables(codebooks_v: jax.Array, queries: jax.Array) -> jax.Array:
    """ADC tables for every codebook slot.

    codebooks_v: (V, m, ksub, dsub); queries: (Q, d).
    Returns (Q, V, m, ksub) f32 with ``T[q,s,j,k] = ||cb||^2 - 2 q_j.cb``
    so that ``sum_j T[q, s, j, code_j]`` follows the repo-wide score
    convention ``||v||^2 - 2 q.v`` on the decoded vector.
    """
    V, m, ksub, dsub = codebooks_v.shape
    Q = queries.shape[0]
    qs = queries.astype(jnp.float32).reshape(Q, m, dsub)
    cn = jnp.sum(codebooks_v * codebooks_v, axis=-1)        # (V, m, ksub)
    dots = jnp.einsum("qjd,sjkd->qsjk", qs, codebooks_v,
                      precision=ref.HIGHEST)
    return cn[None] - 2.0 * dots


# ---------------------------------------------------------------------------
# codebook training — masked Lloyd per subspace
# ---------------------------------------------------------------------------

def train_codebooks(sample: jax.Array, mask: jax.Array, init: jax.Array,
                    iters: int, *, backend: str = "off") -> jax.Array:
    """Refine codebooks on a (masked) sample, one k-means per subspace.

    sample: (S, d); mask: (S,) bool; init: (m, ksub, dsub) warm-start
    codebooks (the streaming-updates regime: each re-train refines the
    previous generation on fresh data; empty clusters keep their old
    centroid instead of collapsing).  The assignment step reuses the
    ``kernels/kmeans_assign`` op per subspace; ``backend`` follows the
    repo-wide dispatch.  Subspaces run one after another (``lax.map``):
    the compiled kernel under ``vmap`` returned wrong assignments on a
    TPU v5e (left rows at the BIG sentinel) while the unbatched kernel
    matched its twin, and m small calls cost next to nothing.
    """
    m, ksub, dsub = init.shape
    S = sample.shape[0]
    pts = sample.astype(jnp.float32).reshape(S, m, dsub).transpose(1, 0, 2)

    def lloyd(points, cents):                # (S, dsub), (ksub, dsub)
        def body(_, cents):
            assign, _ = ops.kmeans_assign(points, cents, mask,
                                          backend=backend)
            tgt = jnp.where(mask, assign, ksub)  # masked rows dropped
            sums = jnp.zeros((ksub, dsub), jnp.float32).at[tgt].add(
                points, mode="drop")
            counts = jnp.zeros((ksub,), jnp.float32).at[tgt].add(
                1.0, mode="drop")
            new = sums / jnp.maximum(counts, 1.0)[:, None]
            return jnp.where(counts[:, None] > 0, new, cents)

        return jax.lax.fori_loop(0, iters, body, cents)

    return jax.lax.map(lambda pc: lloyd(*pc),
                       (pts, init.astype(jnp.float32)))


def _kmeanspp(points: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """k-means++ seeding of one subspace: (n, dsub) -> (k, dsub).

    Each next centroid is drawn with probability proportional to its
    squared distance from the nearest one chosen so far, so every
    well-separated region of the sample gets centroids in proportion to
    its spread.  (Seeding from random sample rows leaves a region that
    drew a single row with one centroid — Lloyd cannot move others
    across the gap — and every vector there then shares one code.)"""
    n = points.shape[0]
    first = points[jax.random.randint(key, (), 0, n)]
    d2 = jnp.sum((points - first) ** 2, -1)
    cents = jnp.zeros((k, points.shape[1]), jnp.float32).at[0].set(first)

    def body(i, carry):
        cents, d2 = carry
        # all-zero distances (k > distinct points): draw uniformly
        logits = jnp.where(jnp.any(d2 > 0), jnp.log(d2), 0.0)
        # jax.random.categorical's Gumbel-max draw, with an argmax that
        # does not narrow to bf16 on a TPU (same draw as on the CPU)
        g = jax.random.gumbel(jax.random.fold_in(key, i), logits.shape)
        c = points[ref.exact_argmax(g + logits)]
        return (cents.at[i].set(c),
                jnp.minimum(d2, jnp.sum((points - c) ** 2, -1)))

    cents, _ = jax.lax.fori_loop(1, k, body, (cents, d2))
    return cents


def init_codebooks(vectors: jax.Array, m: int, ksub: int, iters: int,
                   key: jax.Array, *, backend: str = "off") -> jax.Array:
    """Generation-0 codebooks from a seed sample (build time):
    k-means++ seeding per subspace, then Lloyd refinement."""
    n, d = vectors.shape
    dsub = d // m
    pts = vectors.astype(jnp.float32).reshape(n, m, dsub).transpose(1, 0, 2)
    init = jax.vmap(_kmeanspp, in_axes=(0, None, 0))(
        pts, ksub, jax.random.split(key, m))
    mask = jnp.ones((n,), bool)
    return train_codebooks(vectors, mask, init, iters, backend=backend)


# ---------------------------------------------------------------------------
# background re-train round (scheduled from UBISDriver.tick())
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def retrain_round(state, cfg, key):
    """Train the next codebook generation and install it in the oldest
    slot — one device program, float plane untouched.

    Steps: (1) sample up to ``cfg.pq_sample`` live vectors; (2) warm-start
    Lloyd from the active codebooks; (3) postings still pinned to the
    evicted slot are re-encoded under the new generation (their old
    codebook is being overwritten — everything else upgrades lazily);
    (4) rotate ``pq_active``.  Touches only codes/codebooks/slots, so the
    live id->vector multiset and search visibility cannot change
    (property-tested in tests/test_background_round.py).

    Cold-tier interplay (``cfg.use_tier``): step (3) re-encodes from the
    DEVICE float tiles — a spilled posting's tile is zeroed, so the
    drivers promote any spilled posting pinned to the evicted slot
    *before* calling this round (``_promote_retrain_pinned``), and the
    training sample masks spilled rows out explicitly (their zeroed
    device rows would otherwise collapse the codebooks toward 0).

    A model-sharded state runs the same three steps as separate
    programs (``core.sharded.make_sharded_retrain``).
    """
    sample, smask = retrain_sample(state, cfg, key)
    new_cb = train_codebooks(sample, smask,
                             state.pq_codebooks[state.pq_active],
                             cfg.kmeans_iters, backend=cfg.use_pallas)
    return install_codebooks(state, cfg, new_cb)


def retrain_sample(state, cfg, key):
    """Step (1) of :func:`retrain_round`: (sample (S, d) f32, mask (S,))."""
    M, C, d = state.vectors.shape
    S = cfg.pq_sample

    # spilled postings' device rows are zeroed (cold tier) — exclude
    # them from the training sample or the codebooks collapse on zeros
    flat_valid = (state.slot_valid
                  & ~state.tier_spilled[:, None]).reshape(-1)
    # uniform draw over the LIVE rows: random keys, invalid rows pushed
    # past every valid one, take the first S — an unbiased sample even
    # when live rows cluster at low posting ids (low flat indices)
    keys = jax.random.uniform(key, (M * C,))
    order = jnp.argsort(jnp.where(flat_valid, keys, 2.0))[:S]
    sample = state.vectors.reshape(M * C, d)[order].astype(jnp.float32)
    return sample, flat_valid[order]


def install_codebooks(state, cfg, new_cb):
    """Steps (3)-(4) of :func:`retrain_round`: install ``new_cb`` in the
    oldest slot, re-encode the postings pinned to it, rotate."""
    from ..core.update import dataclasses_replace
    M = state.vectors.shape[0]
    V = cfg.pq_versions
    evict = (state.pq_active + 1) % V

    codebooks = state.pq_codebooks.at[evict].set(new_cb)
    gen = state.pq_slot_gen[state.pq_active] + jnp.uint32(1)
    slot_gen = state.pq_slot_gen.at[evict].set(gen)

    pinned = state.allocated & (state.pq_posting_slot == evict)
    n_pinned = jnp.sum(pinned)
    # steady-state churn lazily upgrades most postings to the active
    # slot, so the pinned set is usually small: gather it into a fixed
    # budget and encode only those tiles; the full-index encode is the
    # rare fallback (cold index where nothing was rewritten since the
    # evicted generation was active)
    R = min(M, 128)

    def reencode_few(codes):
        order = jnp.argsort(~pinned, stable=True)[:R]   # pinned first
        sel = pinned[order]
        fresh = encode_tiles(new_cb,
                             state.vectors[order].astype(jnp.float32))
        return codes.at[jnp.where(sel, order, M)].set(fresh, mode="drop")

    def reencode_all(codes):
        fresh = encode_tiles(new_cb, state.vectors.astype(jnp.float32))
        return jnp.where(pinned[:, None, None], fresh, codes)

    codes = jax.lax.cond(
        n_pinned == 0, lambda c: c,
        lambda c: jax.lax.cond(n_pinned <= R, reencode_few, reencode_all,
                               c),
        state.codes)
    posting_slot = jnp.where(pinned, evict, state.pq_posting_slot)
    return dataclasses_replace(
        state, codes=codes, pq_codebooks=codebooks, pq_slot_gen=slot_gen,
        pq_active=evict, pq_posting_slot=posting_slot)
