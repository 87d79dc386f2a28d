"""Live copy-risk scoring: online SSCD gen↔train similarity.

Both source papers (CVPR'23 "Diffusion Art or Digital Forgery?" and
"Understanding and Mitigating Copying in Diffusion Models") measure
replication as the SSCD similarity between a generation and its nearest
training image — but in this repo that number only existed in offline
``eval/`` and ``search/`` batch jobs, long after the fact. This module is
the online form: a :class:`CopyRiskIndex` holds a train-set embedding dump
device-resident and scores batches of generated images as they are
produced, so "is this generation a copy?" is answered *while serving* (the
``copy_risk`` response field + ``POST /check``) and *while training* (the
sample-hook's ``risk/*`` gauges) instead of in a retrospective report.

Design constraints, inherited from the serving/telemetry substrate:

- **index dumps interoperate**: :func:`load_risk_dump` reads the
  ``search/embed.py`` ``.npz`` format *and* the reference toolchain's
  pickle ``{'features', 'indexes'}`` dumps, and applies the warmcache
  verify-before-load discipline — a corrupt/malformed dump is quarantined
  (``<name>.quarantined.<pid>.<ts>``), counted, and reported as a typed
  :class:`RiskIndexError`, never half-loaded;
- **no new compile surfaces slip past the budget**: the query embedder is
  the *existing* ``eval/embed`` surface (:func:`eval.features.
  make_extractor`) and the top-k matmul is the registered ``risk/score``
  surface; both resolve through :mod:`dcr_tpu.core.warmcache`, so a warm
  respawn scores with ZERO XLA compiles and ``trace_report --max-compiles``
  budgets hold with scoring enabled;
- **scoring never perturbs generation**: images are scored on host copies
  AFTER the sampler ran — bit-identical outputs with scoring on or off —
  and every scoring failure degrades to unscored responses with a
  ``copy_risk/*`` counter, never a failed batch;
- **fixed shapes**: extractor and scorer compile once at a fixed batch
  shape (pad-and-mask), the same one-program-per-shape rule as the serve
  samplers.

Similarity is cosine: index features are L2-normalized at load and query
embeddings inside the jitted scorer, so ``max_sim`` is in [-1, 1] and an
exact pixel match scores ~1.0 regardless of the dump's normalization.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from dcr_tpu.core import warmcache
from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import MeshConfig, RiskConfig

log = logging.getLogger("dcr_tpu")

#: SSCD embedding width (models/resnet.py SSCDModel default); dumps with a
#: different width fail verification loudly instead of mis-matmuling.
EMBED_DIM = 512


class RiskIndexError(RuntimeError):
    """The train-embedding dump could not be loaded/verified. The serve
    worker maps this to risk status "failed" (scoring disabled, admission
    unaffected)."""


class RiskUnavailableError(RuntimeError):
    """A /check-style query arrived while no loaded index can serve it
    (status absent/loading/failed) — mapped to HTTP 503 by the front end."""

    def __init__(self, msg: str, status: str = "absent"):
        super().__init__(msg)
        self.status = status


# ---------------------------------------------------------------------------
# Dump loading: verify before use, quarantine on damage
# ---------------------------------------------------------------------------

def verify_risk_dump(features: np.ndarray, keys: Sequence[str]) -> np.ndarray:
    """Structural checks a dump must pass BEFORE anything downstream touches
    it; returns float32 features. Raises RiskIndexError naming the defect."""
    features = np.asarray(features)
    if features.ndim != 2 or features.shape[0] == 0:
        raise RiskIndexError(
            f"embedding dump features must be a non-empty [N, D] matrix, "
            f"got shape {features.shape}")
    if features.shape[1] != EMBED_DIM:
        raise RiskIndexError(
            f"embedding dump width {features.shape[1]} != SSCD embed dim "
            f"{EMBED_DIM} — wrong backbone or truncated dump")
    features = features.astype(np.float32, copy=False)
    if not np.isfinite(features).all():
        raise RiskIndexError("embedding dump contains non-finite features")
    if len(keys) != features.shape[0]:
        raise RiskIndexError(
            f"embedding dump has {features.shape[0]} features but "
            f"{len(keys)} indexes — torn dump")
    return features


def load_risk_dump(path: str | Path, *,
                   quarantine: bool = True) -> tuple[np.ndarray, list[str]]:
    """Read + verify a train-embedding dump (.npz or reference pickle).

    The warmcache verify-before-load discipline, adapted for USER inputs:
    a file that cannot be parsed at all (truncated zip, bit-flipped pickle)
    is genuinely corrupt and gets quarantine-renamed so the next
    incarnation doesn't retry a known-bad dump forever — but a *readable*
    dump that merely fails verification (wrong embedding width, torn
    features/indexes, non-finite rows) is left IN PLACE: it may be a valid
    artifact of the wrong kind (a CLIP dump, a half-finished embed job a
    rerun will replace), it may be shared by a whole fleet, and renaming it
    would destroy a possibly-expensive input over a misconfiguration.
    Every failure bumps a ``copy_risk/*`` counter and raises a typed
    :class:`RiskIndexError`.
    """
    from dcr_tpu.search.embed import load_embeddings

    path = Path(path)
    if not path.exists():
        raise RiskIndexError(f"no embedding dump at {path}")
    try:
        features, keys = load_embeddings(path)
    except Exception as e:  # unreadable/unpicklable/corrupt-zip damage
        _quarantine_dump(path, repr(e), quarantine)
        raise RiskIndexError(f"corrupt embedding dump {path}: {e!r}") from e
    try:
        features = verify_risk_dump(features, keys)
    except RiskIndexError as e:
        R.log_event("risk_index_invalid", path=str(path), error=str(e))
        R.bump_counter("copy_risk/index_invalid_total")
        raise
    return features, [str(k) for k in keys]


def _quarantine_dump(path: Path, reason: str, quarantine: bool) -> None:
    R.log_event("risk_index_corrupt", path=str(path), error=reason)
    R.bump_counter("copy_risk/index_corrupt_total")
    if quarantine:
        from dcr_tpu.search.embed import quarantine_sidecar

        dest = warmcache.quarantine_rename(path)
        quarantine_sidecar(path)
        if dest is not None:
            log.warning("copyrisk: quarantined corrupt dump %s -> %s",
                        path, dest.name)


# ---------------------------------------------------------------------------
# Compile surfaces
# ---------------------------------------------------------------------------

@compile_surface("risk/score")
def make_risk_scorer(top_k: int):
    """Jitted ``(index_feats [N, D], q [B, D]) -> (sims [B, K], idx [B, K])``.

    Query rows are L2-normalized inside the program (the index is
    normalized once at load), so similarities are cosine. The index rides
    as an ARGUMENT — device-resident between calls, never baked into the
    executable — which keeps the program reusable across index reloads of
    the same shape and fingerprintable for the compile manifest.
    ``precision=HIGHEST`` as in ``search/topk``, which scores the
    store-backed index: both backends answer with float32 dot products.
    """
    import jax
    import jax.numpy as jnp

    def score(index_feats, q):
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        sims = jnp.matmul(q, index_feats.T,
                          precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(sims, top_k)

    return jax.jit(score)


# ---------------------------------------------------------------------------
# Image preparation: the exact embed-pipeline transform, inline
# ---------------------------------------------------------------------------

def prepare_images(images: np.ndarray, image_size: int) -> np.ndarray:
    """Generated float [B, H, W, 3] images in [0, 1] -> SSCD input batch.

    Mirrors the embedding pipeline's folder transform exactly
    (``search/embed.embed_images``: shorter-side resize to the reference
    256/224 ratio, center crop, ImageNet normalization) INCLUDING the uint8
    round-trip a PNG on disk would take — so an index built by embedding
    saved generations scores a live generation of the same pixels at ~1.0.
    """
    from PIL import Image

    from dcr_tpu.data.dataset import _resize_shorter_side
    from dcr_tpu.eval.features import IMAGENET_NORM, reference_resize_for

    mean = np.asarray(IMAGENET_NORM[0], np.float32)
    std = np.asarray(IMAGENET_NORM[1], np.float32)
    resize_to = reference_resize_for(image_size)
    out = []
    for img in np.asarray(images):
        arr = (np.clip(img, 0.0, 1.0) * 255.0).round().astype(np.uint8)
        with Image.fromarray(arr) as pil:
            pil = _resize_shorter_side(pil, resize_to)
            w, h = pil.size
            left, top = (w - image_size) // 2, (h - image_size) // 2
            pil = pil.crop((left, top, left + image_size, top + image_size))
            arr = np.asarray(pil, np.float32) / 255.0
        out.append((arr - mean) / std)
    return np.stack(out)


def decode_image_b64(body: dict) -> np.ndarray:
    """``POST /check`` body -> float [H, W, 3] image in [0, 1]. ValueError
    (a 400-class error) on anything undecodable — client input must never
    become a 500."""
    import base64
    import io

    from PIL import Image

    data = body.get("image_png_b64") or body.get("image_b64")
    if not isinstance(data, str) or not data:
        raise ValueError(
            "body must carry 'image_png_b64' (base64-encoded PNG/JPEG)")
    try:
        raw = base64.b64decode(data, validate=True)
        with Image.open(io.BytesIO(raw)) as im:
            arr = np.asarray(im.convert("RGB"), np.float32) / 255.0
    except Exception as e:
        raise ValueError(f"undecodable image: {e!r}") from e
    return arr


# ---------------------------------------------------------------------------
# The index
# ---------------------------------------------------------------------------

@dataclass
class RiskScore:
    """One generation's copy-risk verdict."""

    max_sim: float
    top_key: str
    topk: list            # [(train key, sim)] best-first, top_k entries

    def doc(self, threshold: float) -> dict:
        """The wire form (`copy_risk` response field / POST /check body)."""
        return {"max_sim": round(self.max_sim, 6), "top_key": self.top_key,
                "flagged": bool(self.max_sim >= threshold),
                "topk": [[k, round(s, 6)] for k, s in self.topk]}


class CopyRiskIndex:
    """A train-set embedding index + compiled scoring pipeline.

    ``score_batch`` is thread-safe after :meth:`build` (the serve worker
    thread and /check handler threads share one index); ``build`` itself is
    serialized by an internal lock and idempotent.

    Two backends, one API: a **dense** whole-dump-resident index
    (``cfg.index_path`` — the original dcr-watch mode, one ``risk/score``
    matmul over a device-resident operand) or a **store-backed** index
    (``cfg.store_dir`` — dcr-store: a sharded embedding store scored
    segment-by-segment through the mesh-sharded ``search/topk`` engine, so
    the corpus no longer has to fit one device).
    """

    def __init__(self, features: Optional[np.ndarray],
                 keys: Optional[Sequence[str]],
                 cfg: RiskConfig, *, batch: int,
                 warm_dir: str = "", store=None):
        self._store = store           # EmbeddingStoreReader (store mode)
        if store is None:
            features = verify_risk_dump(features, keys)
            norms = np.linalg.norm(features, axis=-1, keepdims=True)
            self._features_host = features / np.maximum(norms, 1e-12)
            self.keys = [str(k) for k in keys]
            n_index = len(self.keys)
        else:
            if store.embed_dim != EMBED_DIM:
                raise RiskIndexError(
                    f"embedding store width {store.embed_dim} != SSCD embed "
                    f"dim {EMBED_DIM} — wrong backbone")
            if store.total <= 0:
                raise RiskIndexError(
                    f"embedding store {store.dir} holds no rows")
            self._features_host = None
            self.keys = []            # never materialized in store mode
            n_index = store.total
        self.cfg = cfg
        self.batch = int(batch)
        self.top_k = min(int(cfg.top_k), n_index)
        self.warm_dir = warm_dir
        self._lock = threading.Lock()
        self._built = False
        self._feats_dev = None
        self._extract = None
        self._score = None
        self._engine = None           # ShardedTopK (store mode)
        self._mesh = None
        # live-tail provider (dcr-live): worker sets this to the ingest
        # pump's ``tail(after_seq)`` so scoring covers acked-but-uncompacted
        # rows; called with the engine snapshot's wal_through so committed
        # + tail is one consistent corpus
        self.live_tail = None
        # dcr-slo: optional sampled shadow-exact recall probe
        # (obs/recall_probe.RecallProbe); worker attaches it when the ANN
        # tier serves so online recall is continuously observed
        self.recall_probe = None

    def __len__(self) -> int:
        return self._store.total if self._store is not None \
            else len(self.keys)

    # -- construction --------------------------------------------------------

    @classmethod
    def load(cls, cfg: RiskConfig, *, batch: int, warm_dir: str = "",
             build: bool = True) -> "CopyRiskIndex":
        """Load ``cfg.store_dir`` (dcr-store sharded store; takes
        precedence) or ``cfg.index_path`` (whole dump), optionally build
        the compiled pipeline eagerly (so a status of "ok" means scoring is
        READY, not hoped-for). Raises :class:`RiskIndexError` on a bad
        dump/store."""
        if cfg.store_dir:
            from dcr_tpu.search.store import EmbeddingStoreReader, StoreError

            try:
                reader = EmbeddingStoreReader(cfg.store_dir)
            except StoreError as e:
                R.log_event("risk_store_invalid", path=cfg.store_dir,
                            error=str(e))
                R.bump_counter("copy_risk/index_invalid_total")
                raise RiskIndexError(
                    f"embedding store {cfg.store_dir}: {e}") from e
            index = cls(None, None, cfg, batch=batch, warm_dir=warm_dir,
                        store=reader)
        else:
            features, keys = load_risk_dump(cfg.index_path)
            index = cls(features, keys, cfg, batch=batch, warm_dir=warm_dir)
        if build:
            index.build()
        return index

    def _sscd_params(self):
        """Backbone params: converted reference weights when configured,
        else the DETERMINISTIC random init (jax.random.key(0)) the embedding
        pipeline uses — self-consistent with dumps it produced."""
        import jax

        from dcr_tpu.models.resnet import init_sscd

        model, params = init_sscd(jax.random.key(0),
                                  image_size=self.cfg.image_size)
        if self.cfg.weights_path:
            from dcr_tpu.models.convert import convert_sscd, load_torch_file

            sd = R.retry_call(
                lambda: load_torch_file(self.cfg.weights_path),
                retry_on=(OSError,), give_up_on=R.NONTRANSIENT_IO,
                name="load_risk_sscd_weights")
            params = convert_sscd(sd)
        return model, params

    def build(self) -> "CopyRiskIndex":
        """Compile (or warm-load) the extractor + scorer and put the index
        on device. Idempotent; safe to call from a background loader thread
        while admission proceeds."""
        import jax
        import jax.numpy as jnp

        from dcr_tpu.eval.features import make_extractor
        from dcr_tpu.parallel import mesh as pmesh

        with self._lock:
            if self._built:
                return self
            cache = warmcache.WarmCache(self.warm_dir) if self.warm_dir \
                else None
            # a LOCAL 1-device mesh on purpose: scoring must never introduce
            # a cross-host collective into serve or the trainer's sample
            # hook (which scores on the primary only)
            mesh = pmesh.make_mesh(MeshConfig(data=1),
                                   devices=jax.devices()[:1])
            self._mesh = mesh
            model, params = self._sscd_params()
            extractor = make_extractor(
                lambda p, x: model.apply({"params": p}, x), params, mesh)
            size = self.cfg.image_size
            images_aval = jax.ShapeDtypeStruct(
                (self.batch, size, size, 3), jnp.float32)
            res = warmcache.aot_compile(
                "eval/embed", extractor.func,
                extractor.args + (images_aval,),
                static_config={"pt_style": "sscd", "arch": "sscd_resnet50",
                               "image_size": size, "batch_size": self.batch,
                               "multiscale": False},
                cache=cache)
            embed = warmcache.guarded(res.fn, extractor.func, "eval/embed")
            # params committed to device ONCE: the hot path must not re-ship
            # the whole backbone on every scored batch
            sscd_params = jax.device_put(extractor.args[0])
            self._extract = lambda imgs: embed(sscd_params, imgs)
            if self._store is not None and self.cfg.ann:
                # dcr-ann scoring: IVF + int8 approximate tier with exact
                # f32 re-ranking. Opt-in (--risk.ann): the candidate set is
                # approximate, so the exact engine stays the default. The
                # index must carry cosine-convention (normalized) rows —
                # the engine refuses otherwise rather than mis-rank.
                from dcr_tpu.search.annindex import AnnEngine

                self._engine = AnnEngine(
                    self._store.dir, mesh=mesh, top_k=self.top_k,
                    nprobe=self.cfg.nprobe, query_batch=self.batch,
                    segment_rows=self.cfg.segment_rows,
                    normalize_queries=True, require_normalized_rows=True,
                    warm_dir=self.warm_dir).build()
                scorer_src = "ann"
            elif self._store is not None:
                # store-backed scoring: the mesh-sharded search/topk engine
                # (cosine: queries normalized in-program, index rows
                # normalized host-side at segment load unless the store was
                # built normalized)
                from dcr_tpu.search.shardindex import ShardedTopK

                self._engine = ShardedTopK(
                    self._store, mesh=mesh, top_k=self.top_k,
                    query_batch=self.batch,
                    segment_rows=self.cfg.segment_rows,
                    normalize_queries=True,
                    normalize_rows=not self._store.normalized,
                    warm_dir=self.warm_dir).build()
                scorer_src = "store"
            else:
                feats_dev = jnp.asarray(self._features_host)
                scorer_jit = make_risk_scorer(self.top_k)
                q_aval = jax.ShapeDtypeStruct((self.batch, EMBED_DIM),
                                              jnp.float32)
                sres = warmcache.aot_compile(
                    "risk/score", scorer_jit, (feats_dev, q_aval),
                    static_config={"top_k": self.top_k,
                                   "index_size": len(self.keys),
                                   "batch": self.batch},
                    cache=cache)
                self._score = warmcache.guarded(sres.fn, scorer_jit,
                                                "risk/score")
                self._feats_dev = feats_dev
                scorer_src = sres.source
            self._built = True
            log.info("copyrisk: index ready — %d train embeddings, batch=%d, "
                     "top_k=%d (extractor %s, scorer %s)", len(self),
                     self.batch, self.top_k, res.source, scorer_src)
        return self

    def refresh_store(self) -> bool:
        """dcr-live: re-open the store against the newest snapshot and
        rebuild the search engine, swapping it in atomically — in-flight
        queries keep the engine (and therefore the snapshot) they started
        with (reader isolation). Same segment geometry, batch and top_k as
        the running engine, so the warm ``search/topk`` program is reused
        with ZERO new compiles. Returns True when a newer snapshot was
        picked up. A compaction racing the rebuild surfaces as the typed
        retryable :class:`~dcr_tpu.search.store.StoreSnapshotChangedError`;
        one retry lands on the newer snapshot."""
        from dcr_tpu.search.shardindex import ShardedTopK
        from dcr_tpu.search.store import (EmbeddingStoreReader,
                                          StoreSnapshotChangedError)

        if self._store is None:
            return False
        with self._lock:
            if not self._built:
                return False
            old = self._engine
            for attempt in (0, 1):
                reader = EmbeddingStoreReader(self._store.dir)
                if (reader.snapshot == self._store.snapshot
                        and reader.total == self._store.total):
                    return False
                try:
                    if self.cfg.ann:
                        from dcr_tpu.search.annindex import AnnEngine

                        # same geometry as the running engine, so the warm
                        # ivf_scan/topk programs are reused, zero compiles
                        engine = AnnEngine(
                            reader.dir, mesh=self._mesh, top_k=self.top_k,
                            nprobe=self.cfg.nprobe,
                            query_batch=self.batch,
                            segment_rows=old.segment_rows,
                            normalize_queries=True,
                            require_normalized_rows=True,
                            warm_dir=self.warm_dir).build()
                    else:
                        engine = ShardedTopK(
                            reader, mesh=self._mesh, top_k=self.top_k,
                            query_batch=self.batch,
                            segment_rows=old.segment_rows,
                            normalize_queries=True,
                            normalize_rows=not reader.normalized,
                            warm_dir=self.warm_dir).build()
                    break
                except StoreSnapshotChangedError as e:
                    if attempt:
                        raise
                    log.info("copyrisk: %s — retrying against the newer "
                             "snapshot", e)
            self._engine = engine
            self._store = reader
            log.info("copyrisk: store refreshed — snapshot v%d, %d rows",
                     reader.snapshot, reader.total)
            tracing.event("risk/store_refreshed", snapshot=reader.snapshot,
                          rows=reader.total)
            return True

    # -- scoring -------------------------------------------------------------

    def score_batch(self, images: np.ndarray) -> list[RiskScore]:
        """Score up to ``batch`` generated images (float [n, H, W, 3] in
        [0, 1]); pads to the compiled batch shape, discards pad rows."""
        return self.score_batch_with_features(images)[0]

    def score_batch_with_features(
            self, images: np.ndarray
    ) -> tuple[list[RiskScore], np.ndarray]:
        """:meth:`score_batch` plus the raw SSCD embeddings [n, 512] it
        scored with — the live-ingest hook (dcr-live) streams these into
        the store, so ingest costs no second extractor pass."""
        if not self._built:
            self.build()
        images = np.asarray(images)
        if images.ndim == 3:
            images = images[None]
        n = images.shape[0]
        if n == 0:
            return [], np.zeros((0, EMBED_DIM), np.float32)
        if n > self.batch:
            raise ValueError(
                f"score_batch of {n} exceeds the compiled batch shape "
                f"{self.batch}")
        prep = prepare_images(images, self.cfg.image_size)
        if n < self.batch:
            prep = np.concatenate(
                [prep, np.repeat(prep[-1:], self.batch - n, axis=0)])
        feats = self._extract(prep)
        feats_n = np.asarray(feats, np.float32)[:n]
        engine = self._engine  # one engine per call: refresh swaps atomically
        if engine is not None:
            sims, key_rows = engine.query(feats_n)
            tail_fn = self.live_tail
            tail_feats = tail_keys = None
            if tail_fn is not None:
                from dcr_tpu.search.shardindex import merge_topk

                tail_feats, tail_keys = tail_fn(engine.reader.wal_through)
                if len(tail_feats):
                    tail_sims, tail_out = engine.query_rows(
                        feats_n, tail_feats, tail_keys)
                    sims, key_rows = merge_topk(sims, key_rows,
                                                tail_sims, tail_out)
            if hasattr(engine, "ann"):
                # dcr-slo: ANN staleness = store rows the inverted lists
                # don't cover yet (committed-but-unfolded + live tail);
                # these rows are still served exactly, but every one is a
                # row the approximate candidate walk cannot return
                stale = max(0, int(engine.reader.total) - int(engine.total))
                if tail_feats is not None:
                    stale += int(len(tail_feats))
                tracing.registry().gauge("ann/staleness_rows").set(stale)
                probe = self.recall_probe
                if probe is not None:
                    try:
                        probe.observe(engine, feats_n, key_rows,
                                      tail_feats=tail_feats,
                                      tail_keys=tail_keys)
                    except Exception:
                        # the probe is observability, scoring is product:
                        # a probe failure is logged, never raised into
                        # the response path
                        log.exception("copyrisk: recall probe failed")
            scores = [RiskScore(max_sim=float(row_sims[0]),
                                top_key=str(row_keys[0]),
                                topk=[(str(k), float(s))
                                      for s, k in zip(row_sims, row_keys)])
                      for row_sims, row_keys in zip(sims, key_rows)]
            return scores, feats_n
        sims, idx = self._score(self._feats_dev, feats)
        sims = np.asarray(sims)[:n]
        idx = np.asarray(idx)[:n]
        out = []
        for row_sims, row_idx in zip(sims, idx):
            topk = [(self.keys[int(i)], float(s))
                    for s, i in zip(row_sims, row_idx)]
            out.append(RiskScore(max_sim=topk[0][1], top_key=topk[0][0],
                                 topk=topk))
        return out, feats_n


# ---------------------------------------------------------------------------
# Shared scoring/telemetry helpers (serve worker + trainer sample hook)
# ---------------------------------------------------------------------------

def observe_scores(scores: Sequence[RiskScore], threshold: float) -> dict:
    """Feed one scored batch into the process-wide telemetry registry
    (``dcr_copy_risk_sim`` summary + ``dcr_copy_risk_*_total`` counters)
    and return the aggregate the caller logs/exports."""
    reg = tracing.registry()
    hist = reg.histogram("copy_risk/sim")
    flagged = 0
    for s in scores:
        hist.observe(s.max_sim)
        if s.max_sim >= threshold:
            flagged += 1
    reg.counter("copy_risk/scored_total").inc(len(scores))
    if flagged:
        reg.counter("copy_risk/flagged_total").inc(flagged)
    sims = [s.max_sim for s in scores]
    return {"scored": len(scores), "flagged": flagged,
            "max_sim": max(sims) if sims else 0.0,
            "mean_sim": float(np.mean(sims)) if sims else 0.0}


class EvidenceRecorder:
    """Bounded flight-recorder-style evidence dumps for flagged generations:
    the image plus a JSON sidecar naming the nearest train key. Bounded per
    process (``risk.max_evidence``); a write failure is counted, never
    raised into the serving path."""

    def __init__(self, directory: Optional[str | Path], max_evidence: int):
        self.dir = Path(directory) if directory else None
        self.max_evidence = int(max_evidence)
        self._count = 0
        self._lock = threading.Lock()

    def record(self, image: np.ndarray, score: RiskScore,
               threshold: float, **context) -> Optional[Path]:
        """Returns the JSON sidecar path, or None when disabled/saturated."""
        if self.dir is None or self.max_evidence <= 0:
            return None
        with self._lock:
            if self._count >= self.max_evidence:
                tracing.registry().counter(
                    "copy_risk/evidence_dropped_total").inc()
                return None
            self._count += 1
            seq = self._count
        try:
            from PIL import Image

            self.dir.mkdir(parents=True, exist_ok=True)
            stem = f"flagged_{seq:04d}_{context.get('request_id', 'x')}"
            arr = (np.clip(np.asarray(image), 0, 1) * 255).round()
            Image.fromarray(arr.astype(np.uint8)).save(
                self.dir / f"{stem}.png")
            doc = {"max_sim": score.max_sim, "top_key": score.top_key,
                   "topk": score.topk, "threshold": threshold,
                   "image": f"{stem}.png", "time": time.time(), **context}
            path = self.dir / f"{stem}.json"
            path.write_text(json.dumps(doc, sort_keys=True) + "\n")
            tracing.registry().counter(
                "copy_risk/evidence_dumped_total").inc()
            return path
        except Exception as e:
            # evidence is diagnostics: a full disk must not fail generation.
            # The budget slot is REFUNDED — a burst of transient write
            # failures must not permanently saturate the recorder while
            # zero evidence files exist (the bound is on evidence kept, not
            # on attempts)
            with self._lock:
                self._count -= 1
            R.log_event("risk_evidence_write_failed", error=repr(e))
            R.bump_counter("copy_risk/evidence_write_failed")
            return None
