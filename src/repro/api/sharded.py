"""Class-sharded LogHD estimator for extreme C (ROADMAP: class-axis
scale-out).

LogHD's asymptotics — O(n*D + C*n) storage for n ~ ceil(log_k C) — make the
class axis the ONLY axis that grows with C, so that is the axis this module
shards.  Layout (``models.sharding.CLASS_SHARDED`` / ``CLASS_REPLICATED``
over a ``launch.mesh.make_class_mesh`` ("data", "class") mesh):

  sharded over "class":  profiles (C, n) rows, codebook (C, n) rows
  replicated:            bundles (n, D), the shared encoder, sigma_inv

No C x D array exists at any point:

  fit      — bundle superposition streams the class axis in fixed-size
             blocks of prototypes (``streaming_build_bundles``); Eq. 9
             refinement touches only (n, D) + batches (``fit_engine``,
             optionally data-parallel over the mesh's "data" axis); profile
             estimation scatter-adds each shard's own rows locally
             (``sharded_estimate_profiles``).
  predict  — queries reduce to the replicated n-dim activation profile
             A(x) = h M^T first; each shard scores only its own profile
             rows in R^n and the shards exchange ONE (score, global-index)
             pair per query (argmax-combine over an all-gather of size
             n_shards x B — never the (B, C) score matrix).

Exactness: the per-class score arithmetic is identical under sharding (each
score is an n-length dot, independent of which shard holds the row) and the
argmax-combine reproduces the global first-max tie-break exactly (rows are
contiguous shard-major; see ``sharded_decode``), so sharded predictions are
bitwise identical to the single-device path.  Fit parity is exact too:
``streaming_build_bundles`` degenerates to ``bundling.build_bundles`` at
small C (single block), refinement is the same fused executable, and
``profiles.segment_profile_means`` is bitwise shift-invariant per row.

The variant registers as ``MODEL_CLASSES["loghd_sharded"]`` for
checkpointing and is reached through the normal front door:
``make_classifier("loghd", ..., class_sharding=S)``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import dispatch
from repro.api.fit_engine import fused_refine_bundles, fused_refine_bundles_dp
from repro.api.models import MODEL_CLASSES, LogHDModel, _shape
from repro.core import codebook as cb
from repro.core.bundling import build_bundles
from repro.core.profiles import activations, segment_profile_means
from repro.core.quantize import QTensor
from repro.hdc.conventional import l2_normalize as _l2n
from repro.launch.mesh import make_class_mesh
from repro.models.sharding import CLASS_REPLICATED, CLASS_SHARDED

__all__ = ["ShardedLogHDModel", "fit_loghd_sharded", "shard_loghd_model",
           "place_sharded", "sharded_decode", "sharded_estimate_profiles",
           "streaming_build_bundles", "class_mesh", "clear_sharded_cache"]


# One compiled executable per (stage statics) x (operand shapes) — the dict
# buckets the statics, jit buckets the shapes (same discipline as
# fit_engine._FIT_JIT_CACHE; tests assert zero retraces across repeated
# fit/predict cycles).
_SHARDED_JIT_CACHE: dict = {}


def _cached(key: tuple, builder: Callable[[], Callable]) -> Callable:
    fn = _SHARDED_JIT_CACHE.get(key)
    if fn is None:
        fn = _SHARDED_JIT_CACHE[key] = builder()
    return fn


@dispatch.register_cache_clearer
def clear_sharded_cache() -> None:
    """Drop the sharded fit/predict executables (also runs on
    ``api.dispatch.clear_cache()``)."""
    _SHARDED_JIT_CACHE.clear()


# Meshes are cached so every stage of a given shard layout (fit placement,
# profile estimation, decode) closes over the SAME mesh object — jit and
# _SHARDED_JIT_CACHE keys then agree by identity.
_MESH_CACHE: dict = {}


def class_mesh(n_class_shards: int, n_data_shards: int = 1):
    """The cached ("data", "class") mesh for one shard layout."""
    key = (int(n_class_shards), int(n_data_shards))
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = _MESH_CACHE[key] = make_class_mesh(key[0], key[1])
    return mesh


def _padded_rows(n_classes: int, n_shards: int) -> int:
    """Class-axis length after padding to a whole number of shard rows."""
    return -(-int(n_classes) // int(n_shards)) * int(n_shards)


def _pad_rows(arr: jax.Array, total: int) -> jax.Array:
    """Zero-pad axis 0 to ``total`` rows (padding rows are dead weight the
    decode masks out and labels never address)."""
    n = arr.shape[0]
    if total == n:
        return arr
    return jnp.pad(arr, ((0, total - n),) + ((0, 0),) * (arr.ndim - 1))


# ------------------------------------------------------------------ decode --

def sharded_decode(profiles: jax.Array, acts: jax.Array, *, n_shards: int,
                   n_classes: int, metric: str = "l2") -> jax.Array:
    """argmax over class-sharded profile rows: (C_pad, n), (B, n) -> (B,).

    Each shard scores its own rows locally — the same expanded-l2 (or cos)
    arithmetic ``profiles.decode_profiles`` uses, each score an n-length
    dot independent of the shard layout — masks its padding rows to -inf,
    and keeps one (best score, global row index) pair per query.  The
    combine all-gathers those (n_shards, B) pairs and takes the first max
    over shards.  Rows are contiguous shard-major, and both argmaxes take
    the FIRST maximum, so ties resolve to the lowest global index — exactly
    ``jnp.argmax`` over the full (B, C) score matrix, which is therefore
    never built.

    >>> import jax.numpy as jnp
    >>> profiles = jnp.array([[0., 0.], [1., 0.], [0., 1.]])
    >>> acts = jnp.array([[0.9, 0.1], [0.1, 1.2]])
    >>> sharded_decode(profiles, acts, n_shards=1, n_classes=3).tolist()
    [1, 2]
    """
    if metric not in ("l2", "cos"):
        raise ValueError(
            f"sharded decode supports l2/cos metrics, not {metric!r} "
            "(gather the model with .gathered() for maha)")
    n_shards = int(n_shards)
    c_pad = profiles.shape[0]
    if c_pad % n_shards:
        raise ValueError(f"padded class axis {c_pad} not divisible by "
                         f"{n_shards} shards")
    c_loc = c_pad // n_shards
    mesh = class_mesh(n_shards)

    def local(p_loc, a):
        if metric == "cos":
            scores = _l2n(a) @ _l2n(p_loc).T                    # (B, c_loc)
        else:
            scores = (2.0 * a @ p_loc.T
                      - jnp.sum(p_loc * p_loc, axis=-1))        # (B, c_loc)
        start = jax.lax.axis_index("class") * c_loc
        gidx = start + jnp.arange(c_loc, dtype=jnp.int32)       # global rows
        scores = jnp.where(gidx[None, :] < n_classes, scores, -jnp.inf)
        loc = jnp.argmax(scores, axis=-1)                       # (B,)
        best = jnp.take_along_axis(scores, loc[:, None], axis=-1)[:, 0]
        all_s = jax.lax.all_gather(best, "class")               # (S, B)
        all_i = jax.lax.all_gather(gidx[loc], "class")          # (S, B)
        win = jnp.argmax(all_s, axis=0)                         # first max
        return jnp.take_along_axis(all_i, win[None, :], axis=0)[0]

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(CLASS_SHARDED, P()), out_specs=P(),
                       check_vma=False)
    return fn(profiles, acts)


# --------------------------------------------------------------------- fit --

def _build_stream_bundles() -> Callable:
    def run(g_blocks, starts, h, y):
        def body(m, blk):
            g_blk, start = blk
            # per-block prototypes: ids outside [0, block) are dropped by
            # the scatter-add, so each block superposes exactly its classes
            protos = _l2n(jax.ops.segment_sum(h, y - start,
                                              num_segments=g_blk.shape[0]))
            return m + jnp.einsum("cn,cd->nd", g_blk, protos), None

        m0 = jnp.zeros((g_blocks.shape[2], h.shape[1]), h.dtype)
        m, _ = jax.lax.scan(body, m0, (g_blocks, starts))
        return _l2n(m)

    return jax.jit(run)


def streaming_build_bundles(h: jax.Array, y: jax.Array, codebook: jax.Array,
                            k: int, *, bipolar: bool = False,
                            block: int = 4096) -> jax.Array:
    """Eq. 4 bundle superposition with the class axis streamed in blocks:
    (N, D), (N,), (C, n) -> (n, D), with O(block * max(n, D)) transients.

    The peak live array is one block of prototypes — never (C, D) — so the
    superposition runs at C = 2^20 in the same footprint as C = 4096.  The
    block size is clamped to C, so at small C the single block IS the plain
    path: same segment-sum prototypes, same (C, n) x (C, D) einsum shape,
    bitwise equal to ``build_bundles(class_prototypes(h, y, C), ...)``.
    """
    c, n = codebook.shape
    block = int(min(block, c))
    n_blocks = -(-c // block)
    g = cb.symbol_weight(jnp.asarray(codebook), k)              # (C, n)
    if bipolar:
        g = 2.0 * g - 1.0
    total = n_blocks * block
    if total != c:
        # padding rows carry zero weight AND zero prototypes (no label ever
        # lands in them), so their einsum contribution is exactly 0.0
        g = jnp.pad(g, ((0, total - c), (0, 0)))
    g_blocks = g.reshape(n_blocks, block, n)
    starts = (jnp.arange(n_blocks) * block).astype(y.dtype)
    fn = _cached(("stream_bundles", bool(bipolar)), _build_stream_bundles)
    return fn(g_blocks, starts, h, y)


def sharded_estimate_profiles(bundles: jax.Array, h: jax.Array,
                              y: jax.Array, n_classes: int,
                              n_shards: int) -> jax.Array:
    """Eq. 6 profile estimation with each shard owning its own rows:
    -> (C_pad, n) sharded over "class".

    Activations (B, n) are computed once, replicated (they are the SMALL
    side of LogHD); each shard then scatter-adds only the examples whose
    label falls in its row range — ``segment_profile_means`` drops
    out-of-range ids and is bitwise shift-invariant per row, so every row
    matches the unsharded ``estimate_profiles`` exactly.  Padding rows (and
    classes absent from the batch) come out zero, the standard degenerate
    profile."""
    n_shards = int(n_shards)
    c_pad = _padded_rows(n_classes, n_shards)
    c_loc = c_pad // n_shards
    acts = activations(bundles, h)                              # (B, n)
    mesh = class_mesh(n_shards)
    # inputs may arrive committed to another mesh (e.g. the wider
    # (data, class) refine mesh when data_sharding > 1) — re-place the small
    # replicated operands onto this stage's mesh before the shard_map
    rep = NamedSharding(mesh, CLASS_REPLICATED)
    acts, y = jax.device_put(acts, rep), jax.device_put(y, rep)

    def build():
        def local(a, ids):
            start = (jax.lax.axis_index("class") * c_loc).astype(ids.dtype)
            return segment_profile_means(a, ids - start, c_loc)

        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P(), P()),
            out_specs=CLASS_SHARDED, check_vma=False))

    fn = _cached(("profiles", n_shards, c_loc), build)
    return fn(acts, y)


# ------------------------------------------------------------------- model --

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class ShardedLogHDModel(LogHDModel):
    """LogHD with profile/codebook rows laid out over a "class" mesh axis.

    Same fields as ``LogHDModel`` plus the static shard layout: the class
    axis is padded to ``class_sharding`` equal row blocks and
    ``n_classes_real`` remembers the true C (0 means no padding).  Both
    extras live in ``aux_fields`` — part of the treedef — so the jit
    predict surface automatically keys one executable per shard layout.
    Decode is ``sharded_decode`` (l2/cos); the Pallas kernels don't know
    this layout, so kernel dispatch is off for the class."""

    class_sharding: int = 1
    n_classes_real: int = 0           # 0: profiles carry no padding rows

    method: ClassVar[str] = "loghd_sharded"
    stored_leaves: ClassVar[tuple] = ("bundles", "profiles")
    aux_fields: ClassVar[tuple] = ("metric", "encoder_kind",
                                   "class_sharding", "n_classes_real")
    kernel_dispatch: ClassVar[bool] = False

    def predict_encoded(self, h: jax.Array) -> jax.Array:
        """Replicated n-dim activations, then the sharded argmax-combine."""
        acts = activations(self.bundles, h)
        return sharded_decode(self.profiles, acts,
                              n_shards=self.class_sharding,
                              n_classes=self.n_classes, metric=self.metric)

    def model_bits(self, bits: int) -> int:
        """Accounting over the REAL class count — padding rows are layout,
        not model."""
        from repro.core.loghd import memory_bits
        n, d = _shape(self.bundles)
        return memory_bits(self.n_classes, d, n, bits)

    @property
    def n_classes(self) -> int:
        return int(self.n_classes_real) or _shape(self.profiles)[0]

    def gathered(self) -> LogHDModel:
        """Collect to a plain single-device ``LogHDModel`` (padding rows
        dropped) — for maha decode, kernel predict, or export."""
        m = self.materialized()
        c = self.n_classes
        return LogHDModel(enc=m.enc, bundles=jnp.asarray(m.bundles),
                          profiles=jnp.asarray(m.profiles)[:c],
                          codebook=jnp.asarray(m.codebook)[:c],
                          sigma_inv=m.sigma_inv, metric=m.metric,
                          encoder_kind=m.encoder_kind)

    def sharded_leaf_bytes(self) -> tuple:
        """(max bytes any one device holds, total logical bytes) over the
        class-sharded leaves (profiles + codebook) — the resident-memory
        number the extreme bench gates on."""
        per_dev: dict = {}
        total = 0
        for name in ("profiles", "codebook"):
            leaf = getattr(self, name)
            arr = leaf.codes if isinstance(leaf, QTensor) else leaf
            total += arr.nbytes
            for s in arr.addressable_shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        return max(per_dev.values()), total

    def resident_bytes_per_device(self) -> dict:
        """Per-device residency vs the ideal C/n_shards split (padding rows
        excluded from the ideal, so the ratio charges them honestly)."""
        mx, total = self.sharded_leaf_bytes()
        c_pad = _shape(self.profiles)[0]
        real = total * self.n_classes / max(c_pad, 1)
        ideal = real / max(int(self.class_sharding), 1)
        return {"max_bytes_per_device": int(mx),
                "total_bytes": int(total),
                "ideal_bytes_per_device": ideal,
                "ratio_to_ideal": mx / ideal}


MODEL_CLASSES[ShardedLogHDModel.method] = ShardedLogHDModel


# -------------------------------------------------------------- placement --

def place_sharded(model: ShardedLogHDModel) -> ShardedLogHDModel:
    """Commit the model onto its class mesh: row leaves sharded, the rest
    replicated (QTensor codes shard with their rows; scales replicate)."""
    mesh = class_mesh(int(model.class_sharding))
    rows = NamedSharding(mesh, CLASS_SHARDED)
    rep = NamedSharding(mesh, CLASS_REPLICATED)

    def put(leaf, sharding):
        if leaf is None:
            return None
        if isinstance(leaf, QTensor):
            return dataclasses.replace(
                leaf, codes=jax.device_put(leaf.codes, sharding),
                scale=jax.device_put(leaf.scale, rep))
        return jax.device_put(leaf, sharding)

    return model.replace(profiles=put(model.profiles, rows),
                         codebook=put(model.codebook, rows),
                         bundles=put(model.bundles, rep),
                         sigma_inv=put(model.sigma_inv, rep))


def shard_loghd_model(model: LogHDModel, n_shards: int, *,
                      place: bool = True) -> ShardedLogHDModel:
    """Re-lay an already-fitted LogHD model over ``n_shards`` class shards.

    Pads the row leaves to the shard grid and (by default) commits them to
    the mesh; predictions are bitwise identical to the source model."""
    if getattr(model, "metric", "l2") == "maha":
        raise ValueError("class-sharded LogHD decodes l2/cos only; keep the "
                         "maha model unsharded or switch its metric")
    m = model.materialized()
    c = _shape(m.profiles)[0]
    c_pad = _padded_rows(c, n_shards)
    out = ShardedLogHDModel(
        enc=m.enc, bundles=m.bundles,
        profiles=_pad_rows(jnp.asarray(m.profiles), c_pad),
        codebook=_pad_rows(jnp.asarray(m.codebook), c_pad),
        sigma_inv=m.sigma_inv, metric=m.metric, encoder_kind=m.encoder_kind,
        class_sharding=int(n_shards), n_classes_real=c)
    return place_sharded(out) if place else out


# ----------------------------------------------------------------- trainer --

def fit_loghd_sharded(cfg, enc_cfg, x: jax.Array, y: jax.Array, *,
                      enc: Optional[dict] = None,
                      encoded: Optional[jax.Array] = None,
                      prototypes: Optional[jax.Array] = None,
                      base=None, key=None) -> ShardedLogHDModel:
    """Algorithm 1 with the class axis sharded end to end.

    Same pipeline, stage for stage, as ``_impl.fit_loghd_model`` — which
    delegates here when ``cfg.class_sharding > 1`` — with the C-sized
    stages swapped for their streaming/sharded forms:

      codebook   — full host build (O(C n) ints; the Eq. 9 targets gather
                   needs arbitrary rows), then padded + row-sharded into
                   the model.  Per-shard row construction is available as
                   ``codebook.build_codebook_rows`` and verified equal.
      bundles    — ``streaming_build_bundles`` (no C x D prototype array).
      refine     — the fused engine; ``cfg.data_sharding > 1`` runs the
                   data-parallel variant over the mesh's "data" axis.
      profiles   — ``sharded_estimate_profiles``, each shard its own rows.

    ``sigma_inv`` is not estimated (maha decode is rejected up front); every
    other stage is exact, so at small C the result is bitwise identical to
    the unsharded trainer."""
    if cfg.metric == "maha":
        raise ValueError("class-sharded LogHD decodes l2/cos only "
                         "(maha needs the dense profile gather)")
    n_shards = max(1, int(getattr(cfg, "class_sharding", 1)))
    data_shards = max(1, int(getattr(cfg, "data_sharding", 1)))
    from repro.api._impl import _encoder_and_encodings
    enc, h = _encoder_and_encodings(enc_cfg, x, enc, encoded)

    c, n = cfg.n_classes, cfg.n_bundles
    book = cb.build_codebook(c, n, cfg.k, alpha=cfg.alpha, seed=cfg.seed,
                             method=cfg.codebook_method)
    book_j = jnp.asarray(book)
    if prototypes is not None:
        bundles = build_bundles(prototypes, book_j, cfg.k,
                                bipolar=cfg.bipolar_init)
    else:
        bundles = streaming_build_bundles(h, y, book_j, cfg.k,
                                          bipolar=cfg.bipolar_init)
    if data_shards > 1:
        bundles = fused_refine_bundles_dp(
            bundles, h, y, book_j, cfg.k, epochs=cfg.refine_epochs,
            lr=cfg.lr, batch_size=cfg.refine_batch,
            mesh=class_mesh(n_shards, data_shards), axis="data",
            seed=cfg.seed, key=key)
    else:
        bundles = fused_refine_bundles(
            bundles, h, y, book_j, cfg.k, epochs=cfg.refine_epochs,
            lr=cfg.lr, batch_size=cfg.refine_batch, seed=cfg.seed, key=key)

    profiles = sharded_estimate_profiles(bundles, h, y, c, n_shards)
    c_pad = _padded_rows(c, n_shards)
    model = ShardedLogHDModel(
        enc=enc, bundles=bundles, profiles=profiles,
        codebook=_pad_rows(book_j, c_pad), sigma_inv=None,
        metric=cfg.metric, encoder_kind=enc_cfg.kind,
        class_sharding=n_shards, n_classes_real=c)
    return place_sharded(model)
