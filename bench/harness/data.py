"""SIFT-shaped vectors from a seed.

SIFT descriptors (ann-benchmarks ``sift-128-euclidean``) are 128
non-negative integers in 0..255 whose intrinsic dimension is far below
128, with local structure at several scales.  This generator gives
that shape without downloading anything:

* a few large clusters (``n_top``), each with its own low-dimensional
  subspace (``rank`` directions) around a sparse, skewed non-negative
  mean, as SIFT's histograms are;
* many small clusters nested in each large one, their centres spread
  along the large cluster's subspace;
* points spread along the same subspace around their small cluster's
  centre, plus isotropic noise, then rounded and clipped to 0..255.

So a point's true nearest neighbours sit near it in its own small
cluster, clearly closer than the rest.  Queries come from the same
distribution (new draws, never base points).  Every seed gives the same
sizes; the seed changes the clusters and the draws.  The clusters are
drawn on the host; the rows on the default device in one jitted call.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 16_384


@dataclasses.dataclass(frozen=True)
class SiftShape:
    dim: int = 128
    n_top: int = 16
    n_sub: int = 256          # small clusters per large one
    rank: int = 16            # directions of a large cluster's subspace
    top_spread: float = 20.0  # small-cluster centres along the subspace
    sub_spread: float = 12.0  # points along the subspace
    noise: float = 6.0        # isotropic noise on every coordinate
    mean_shape: float = 0.5   # gamma shape of the large clusters' means
    mean_scale: float = 40.0

    @classmethod
    def from_json(cls, d: dict) -> "SiftShape":
        return cls(**d)


class SiftLike:
    """The clusters of one seed; ``rows(stream, n)`` draws points."""

    def __init__(self, shape: SiftShape, seed: int):
        self.shape = s = shape
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 0])
        self.top_mean = rng.gamma(s.mean_shape, s.mean_scale,
                                  (s.n_top, s.dim)).astype(np.float32)
        basis = rng.standard_normal((s.n_top, s.dim, s.rank))
        self.basis = (np.linalg.qr(basis)[0]).astype(np.float32)
        w = rng.standard_normal((s.n_top, s.n_sub, s.rank)) * s.top_spread
        self.sub_mean = (self.top_mean[:, None, :]
                         + np.einsum("tdr,tsr->tsd", self.basis, w)
                         ).astype(np.float32)            # (T, S, d)

    def rows(self, stream: int, n: int):
        """``n`` points of one stream (0: the corpus, 1: queries, ...)
        as float32 holding integers 0..255, and the small cluster of
        each (``top * n_sub + sub``).  Made on the default device in one
        jitted call, in chunks of ``CHUNK`` rows, each chunk from its own
        key; then copied to the host."""
        import jax
        import jax.numpy as jnp
        s = self.shape
        seed = np.random.SeedSequence([self.seed, 1 + stream])
        key = jax.random.key(int(seed.generate_state(1)[0]))
        n_chunks = -(-n // CHUNK)
        out, label = _make_rows(key, jnp.asarray(self.sub_mean),
                                jnp.asarray(self.basis), n_chunks, s)
        return np.asarray(out)[:n], np.asarray(label)[:n].astype(np.int64)

    def clustered_order(self, label: np.ndarray) -> np.ndarray:
        """Row order that visits the large clusters one after another in
        a seeded order, and within each its small clusters in turn (the
        clustered runbooks of the big-ann-benchmarks streaming track)."""
        s = self.shape
        perm = np.random.default_rng([self.seed, 99]).permutation(s.n_top)
        top, sub = label // s.n_sub, label % s.n_sub
        return np.lexsort((np.arange(len(label)), sub, perm[top]))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _make_rows(key, sub_mean, basis, n_chunks: int, s: SiftShape):
    def chunk(k):
        kt, ks, kz, kn = jax.random.split(k, 4)
        top = jax.random.randint(kt, (CHUNK,), 0, s.n_top)
        sub = jax.random.randint(ks, (CHUNK,), 0, s.n_sub)
        z = jax.random.normal(kz, (CHUNK, s.rank)) * s.sub_spread
        along = jnp.einsum("nr,ndr->nd", z, basis[top],
                           precision=jax.lax.Precision.HIGHEST)
        x = (sub_mean[top, sub] + along
             + jax.random.normal(kn, (CHUNK, s.dim)) * s.noise)
        return jnp.clip(jnp.rint(x), 0.0, 255.0), top * s.n_sub + sub

    keys = jax.random.split(key, n_chunks)
    x, label = jax.lax.map(chunk, keys)
    return x.reshape(-1, s.dim), label.reshape(-1)
