"""Versioned asset publishing + atomic switch-over (paper §3).

"Indexes can be built in batch offline, and then bulk loaded into a serving
framework. In such a scenario, new indexes can be placed alongside the old,
and then the Lambda instances can be refreshed to switch over to the new
indexes."

Layout in the object store:

    assets/<name>/versions/<version>/...files...
    assets/<name>/segments/<seg>/...files...   <- immutable segment data (NRT)
    assets/<name>/MANIFEST            <- tiny JSON pointer {"current": version}

Publishing writes the new version's files *alongside* the old, then swaps the
manifest with a conditional put (etag compare-and-set) so concurrent
publishers cannot interleave. Serving instances resolve the manifest on cold
start; ``refresh()`` invalidates hydration caches so the next invocation on
each instance re-resolves — exactly the paper's "Lambda instances can be
refreshed" story, with zero downtime (old version stays readable throughout).

Near-real-time indexing rides the same seam as *generations*: a generation
is a tiny manifest version (``generation.json``) that REFERENCES immutable
segments published under ``segments/`` — one base segment plus an ordered
delta tier — with a tombstone set for deletes and the live corpus-wide BM25
stats/vocab. Committing a batch publishes only the new delta's bytes (the
Airphant-style small-immutable-increment story) and the shared state as a
delta of the last (:class:`GenState`), then CAS-flips the manifest; a torn publish between two concurrent writers surfaces as
:class:`PublishConflict` on the loser, never as a half-visible generation.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict

import numpy as np

from repro.core import jsonutil as orjson   # orjson when installed

from repro.core.directory import (Directory, RamDirectory, StoreDirectory,
                                  copy_directory)
from repro.core.object_store import NoSuchKey, ObjectStore, PreconditionFailed


class PublishConflict(Exception):
    pass


GENERATION_FILE = "generation.json"
STATE_DELTA_FILE = "delta.json"
# a shared state is written as a delta of its parent until the chain back
# to the last whole-vocabulary snapshot is this long; then a snapshot again
STATE_SNAPSHOT_EVERY = 64
# the device idf is padded to a multiple of this, with at least this much
# room for new terms, so that generations share their search programs
IDF_HEADROOM = 1 << 14
STATE_MEMO = 8          # resolved generation states a catalog keeps


def _idf_cap(n_terms: int) -> int:
    return -(-n_terms // IDF_HEADROOM) * IDF_HEADROOM + IDF_HEADROOM


class GenState:
    """One generation's shared scoring state, resolved: the live document
    count and token total, df by term id, the vocabulary, and (derived on
    demand) idf on the host and on the device.

    A state is published either whole (a snapshot: ``stats.json`` and
    ``vocab.json``, the ``compute_global_stats`` dict and its vocab) or as
    a delta of its parent (``delta.json``: the new terms, the df of every
    term whose df changed, the counts). Resolving a delta applies it to
    the parent's resolved state, so a generation costs what its commit
    changed. Along a chain the vocabulary dict and term list are SHARED
    and append-only: this generation's terms are the first ``n_terms``.
    The device idf is padded to ``idf_cap``, which a child keeps while its
    terms fit, so that every generation's search program is the same."""

    def __init__(self, gen: int, seg: str, chain: list, n_docs: int,
                 total_len: int, fields, vocab: dict, terms: list,
                 df: np.ndarray, idf_cap: int) -> None:
        self.gen, self.seg, self.chain = gen, seg, chain
        self.n_docs, self.total_len, self.fields = n_docs, total_len, fields
        self.vocab, self.terms, self.df = vocab, terms, df
        self.n_terms = len(df)
        self.idf_cap = idf_cap
        self._idf = None
        self._device_idf = None
        self._lock = threading.Lock()

    @property
    def avgdl(self) -> float:
        return self.total_len / max(1, self.n_docs)

    @classmethod
    def from_snapshot(cls, gen: int, seg: str, stats: dict,
                      vocab: dict) -> "GenState":
        terms: list = [None] * len(vocab)
        for t, i in vocab.items():
            terms[i] = t
        df_map = stats["df"]
        df = np.fromiter((df_map.get(t, 0) for t in terms), np.int64,
                         count=len(terms))
        n = int(stats["n_docs"])
        total = stats.get("_total_len")
        if total is None:
            total = round(stats["avgdl"] * max(1, n)) if n else 0
        return cls(gen, seg, [seg], n, int(total), stats.get("fields"),
                   dict(vocab), terms, df, _idf_cap(len(terms)))

    def child(self, seg: str, delta: dict) -> "GenState":
        """The state ``delta`` (a ``delta.json``) makes of this one."""
        new = delta["new_terms"]
        vocab, terms = self.vocab, self.terms
        if len(terms) != self.n_terms:
            # another chain already grew the shared vocabulary past this
            # state (two writers raced a generation): fork a copy
            terms = terms[:self.n_terms]
            vocab = {t: i for i, t in enumerate(terms)}
        for t in new:
            vocab[t] = len(terms)
            terms.append(t)
        df = np.concatenate([self.df, np.asarray(delta["new_df"], np.int64)])
        df[np.asarray(delta["df_ids"], np.int64)] = delta["df_vals"]
        cap = (self.idf_cap if len(df) <= self.idf_cap
               else _idf_cap(len(df)))
        return GenState(int(delta["gen"]), seg, list(delta["chain"]),
                        int(delta["n_docs"]), int(delta["total_len"]),
                        delta.get("fields"), vocab, terms, df, cap)

    def delta_of(self, gen: int, seg: str, stats) -> "dict | None":
        """``stats`` (a :class:`~repro.index.builder.LiveStats` grown from
        this state) as a ``delta.json`` of this state, or None where it
        was not grown from it. The changed df are found by one vectorized
        compare."""
        n0 = self.n_terms
        if (stats.n_terms < n0 or len(self.chain) >= STATE_SNAPSHOT_EVERY
                or (n0 and stats.terms[n0 - 1] != self.terms[n0 - 1])):
            return None
        cur = stats.df
        changed = np.flatnonzero(cur[:n0] != self.df)
        return {"gen": gen, "prev": self.seg, "chain": self.chain + [seg],
                "n_docs": stats.n_docs, "total_len": stats.total_len,
                "fields": stats.fields, "new_terms": stats.terms[n0:],
                "new_df": cur[n0:].tolist(), "df_ids": changed.tolist(),
                "df_vals": cur[changed].tolist()}

    def idf(self) -> np.ndarray:
        """(n_terms,) float32, the formula ``combine_segments`` applies."""
        with self._lock:
            if self._idf is None:
                df = self.df.astype(np.float64)
                self._idf = np.log(1.0 + (self.n_docs - df + 0.5)
                                   / (df + 0.5)).astype(np.float32)
            return self._idf

    def device_idf(self) -> tuple:
        """(idf on the device, padded with zeros to ``idf_cap``; the bytes
        this call placed there): one copy serves every partition."""
        idf = self.idf()
        with self._lock:
            if self._device_idf is not None:
                return self._device_idf, 0
            import jax
            host = np.zeros(self.idf_cap, np.float32)
            host[:len(idf)] = idf
            self._device_idf = jax.device_put(host)
            return self._device_idf, host.nbytes

    def vocab_view(self) -> dict:
        """This generation's term -> id map (a copy where the shared one
        has grown past it)."""
        if len(self.vocab) == self.n_terms:
            return self.vocab
        return {t: i for i, t in enumerate(self.terms[:self.n_terms])}

    def as_stats(self) -> dict:
        """The ``compute_global_stats`` dict (O(vocabulary))."""
        out = {"n_docs": self.n_docs, "avgdl": self.avgdl,
               "df": {self.terms[i]: int(self.df[i])
                      for i in np.flatnonzero(self.df)},
               "_total_len": self.total_len}
        if self.fields is not None:
            out["fields"] = self.fields
        return out


def generation_version(gen: int) -> str:
    """Canonical version string for generation ``gen``. Zero-padding makes
    typical listings read in order, but all ORDERING logic must go through
    :func:`parse_generation` — lexical comparison has a cliff at the first
    generation wider than the pad (gen-1000000 sorts before gen-999999)."""
    return f"gen-{gen:06d}"


def parse_generation(version: str) -> int | None:
    """Numeric generation of a ``gen-*`` version string, else None."""
    if version.startswith("gen-"):
        try:
            return int(version[4:])
        except ValueError:
            return None
    return None


@dataclasses.dataclass
class GenerationManifest:
    """One generation of a NRT-updated asset: base + ordered deltas +
    tombstones, plus the LIVE corpus-wide scoring state.

    The scoring state — ``stats`` (n_docs/avgdl/df over live documents)
    and ``vocab`` — is generation-level, not segment-level: segment blocks
    store only tf and doc lengths (stat-independent), and idf/avgdl are
    applied at QUERY time from this state — Lucene's move of computing idf
    from the live IndexReader. That is the invariant that keeps a
    delta-served index exactly rank-identical to a from-scratch rebuild of
    the final corpus; a frozen-idf delta would drift as the corpus grows.

    The state may be INLINE (``stats``/``vocab``) or SHARED
    (``stats_ref = [asset, segment]`` pointing at one stats segment in the
    catalog). Shared is what a partitioned fleet publishes: the global
    df/vocab are identical for every partition, so inlining them would
    store O(partitions × generations) copies of the whole vocabulary —
    the manifest would outweigh the delta it describes. Resolve with
    :meth:`AssetCatalog.resolve_generation_state`.
    """

    gen: int                       # monotonically increasing generation number
    base: str                      # base segment id (under segments/)
    deltas: list[str]              # ordered delta segment ids
    tombstones: list[int]          # deleted INTERNAL doc positions (stable:
    #                                base+delta order; a re-add gets a fresh
    #                                position, so old tombstones can't kill it)
    stats: dict | None = None      # inline live {"n_docs", "avgdl", "df"}
    vocab: dict | None = None      # inline frozen append-only term -> id map
    stats_ref: list | None = None  # OR shared: [asset, segment] in the catalog
    # dense-vector tier (hybrid retrieval): the SAME base+delta shape as the
    # BM25 tier, row positions aligned with it doc-for-doc, so ONE tombstone
    # list and ONE generation number govern both tiers. None = no dense tier
    # (pre-hybrid manifests parse unchanged).
    vec_base: str | None = None
    vec_deltas: list = dataclasses.field(default_factory=list)

    def to_json(self) -> bytes:
        return orjson.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, data: bytes) -> "GenerationManifest":
        return cls(**orjson.loads(data))

    @property
    def segments(self) -> list[str]:
        return [self.base] + list(self.deltas)

    @property
    def vec_segments(self) -> list[str]:
        """Dense-tier segment ids, base first ([] when no dense tier)."""
        if self.vec_base is None:
            return []
        return [self.vec_base] + list(self.vec_deltas)


class AssetCatalog:
    def __init__(self, store: ObjectStore, root: str = "assets") -> None:
        self.store = store
        self.root = root.rstrip("/")
        # resolved shared states, by (asset, segment): segments are
        # immutable, so a resolution never goes stale
        self._states: "OrderedDict[tuple, GenState]" = OrderedDict()
        self._states_lock = threading.Lock()

    # -- paths -----------------------------------------------------------------

    def _manifest_key(self, name: str) -> str:
        return f"{self.root}/{name}/MANIFEST"

    def version_prefix(self, name: str, version: str) -> str:
        return f"{self.root}/{name}/versions/{version}/"

    def segment_prefix(self, name: str, seg: str) -> str:
        return f"{self.root}/{name}/segments/{seg}/"

    # -- publish (the offline batch-indexing side) --------------------------------

    def publish(self, name: str, version: str, files: Directory) -> str:
        """Upload `files` as a new version and atomically flip the manifest."""
        prefix = self.version_prefix(name, version)
        copy_directory(files, self.store, prefix)
        # compare-and-set the manifest
        try:
            cur = self.store.head(self._manifest_key(name))
            if_etag = cur.etag
        except NoSuchKey:
            if_etag = ""
        body = orjson.dumps({"current": version})
        try:
            self.store.put(self._manifest_key(name), body, if_etag=if_etag)
        except PreconditionFailed as e:
            raise PublishConflict(f"concurrent publish of {name!r}") from e
        return version

    def versions(self, name: str) -> list[str]:
        prefix = f"{self.root}/{name}/versions/"
        seen = []
        for meta in self.store.list(prefix):
            v = meta.key[len(prefix):].split("/", 1)[0]
            if v not in seen:
                seen.append(v)
        return seen

    def gc(self, name: str, keep: int = 2) -> list[str]:
        """Delete all but the newest `keep` versions (old one kept for
        rollback — the 'new indexes placed alongside the old' invariant).
        The CURRENT (serving) version is never deleted, whatever ``keep``
        says. Generation manifests additionally pin their segments: after
        pruning versions, any segment no surviving generation references is
        reclaimed too (a merged-away delta tier stops costing storage)."""
        current = self.current_version(name)
        # oldest-first, numerically for generations (lexical order has a
        # cliff when the gen number outgrows its zero-pad)
        vs = sorted(self.versions(name),
                    key=lambda v: (0, parse_generation(v))
                    if parse_generation(v) is not None else (1, v))
        doomed = [v for v in vs if v != current][: max(0, len(vs) - keep)]
        for v in doomed:
            for meta in self.store.list(self.version_prefix(name, v)):
                self.store.delete(meta.key)
        self._gc_segments(name)
        return doomed

    def _gc_segments(self, name: str) -> list[str]:
        """Reclaim segments referenced by NO surviving generation manifest.
        No-op for plain-segment assets (no generation manifests)."""
        live: set[str] = set()
        saw_generation = False
        for v in self.versions(name):
            d = StoreDirectory(self.store, self.version_prefix(name, v))
            if GENERATION_FILE not in d.list():
                continue
            saw_generation = True
            m = self.read_generation(name, v)
            live.update(m.segments)
            live.update(m.vec_segments)
        if not saw_generation:
            return []
        return self.sweep_unreferenced(name, live)

    def sweep_unreferenced(self, name: str, live: "set[str]") -> list[str]:
        """Delete every segment of ``name`` whose id is not in ``live``.
        The one segment-sweeping rule — shared by the catalog's own gc and
        any coordinator-level sweep (e.g. the fleet writer's shared
        stats/vocab segments), so key-layout changes can't diverge."""
        doomed = []
        prefix = f"{self.root}/{name}/segments/"
        for meta in self.store.list(prefix):
            seg = meta.key[len(prefix):].split("/", 1)[0]
            if seg not in live:
                self.store.delete(meta.key)
                if seg not in doomed:
                    doomed.append(seg)
        return doomed

    # -- generations (the NRT incremental-indexing side) ---------------------------

    def publish_segment(self, name: str, seg: str, files: Directory) -> str:
        """Upload one immutable segment's files under ``segments/<seg>/``.
        No manifest flip: a segment is invisible until a generation
        manifest referencing it is published.

        Segments are IMMUTABLE — publishing an id that already exists is
        refused as a :class:`PublishConflict`. Without this, two writers
        racing the same generation number would silently overwrite each
        other's segment BYTES before the manifest CAS picks a winner, and
        the winner's manifest could end up serving the loser's documents."""
        prefix = self.segment_prefix(name, seg)
        if self.store.list(prefix):
            raise PublishConflict(
                f"{name!r}: segment {seg!r} already published — segments "
                "are immutable; a racing writer owns this id")
        copy_directory(files, self.store, prefix)
        return seg

    def open_segment(self, name: str, seg: str, *,
                     block_size: int = 1 << 20) -> StoreDirectory:
        return StoreDirectory(self.store, self.segment_prefix(name, seg),
                              block_size=block_size)

    def publish_generation(self, name: str,
                           manifest: GenerationManifest) -> str:
        """Publish ``manifest`` as version ``gen-<gen>`` and CAS-flip the
        asset manifest to it.

        Two conflict classes, both surfaced as :class:`PublishConflict`:

        * a STALE BASE — the asset already serves ``manifest.gen`` or newer,
          so this writer built its delta against a superseded generation
          (checked against the manifest read below, not at an earlier
          instant, so sequential lost-update races are caught too);
        * a TORN PUBLISH — the asset manifest changed between that read and
          our conditional put (two writers racing the same flip); the etag
          compare-and-set lets exactly one land.

        The loser's generation files are cleaned up (no phantom generation
        for gc to mistake for live state); it must re-read the current
        generation, rebase its delta, and retry."""
        version = generation_version(manifest.gen)
        key = self._manifest_key(name)
        try:
            if_etag = self.store.head(key).etag
            current = orjson.loads(self.store.get(key))["current"]
        except NoSuchKey:
            if_etag, current = "", None
        cur_gen = parse_generation(current) if current is not None else None
        if cur_gen is not None and cur_gen >= manifest.gen:
            raise PublishConflict(
                f"{name!r}: generation {version} is not newer than the "
                f"published {current} — rebase the delta and retry")
        # create-once: two writers racing the SAME generation number would
        # otherwise write the same key, and the CAS loser's cleanup would
        # delete the file the WINNER's flip now serves. The conditional
        # create makes the generation directory exclusively ours — losing
        # THIS race is a conflict before anything else is touched.
        gen_key = self.version_prefix(name, version) + GENERATION_FILE
        try:
            self.store.put(gen_key, manifest.to_json(), if_etag="")
        except PreconditionFailed as e:
            raise PublishConflict(
                f"{name!r}: generation {version} already published by a "
                "concurrent writer — rebase the delta and retry") from e
        try:
            self.store.put(key, orjson.dumps({"current": version}),
                           if_etag=if_etag)
        except PreconditionFailed as e:
            # we exclusively own gen_key (create-once above), so deleting
            # it cannot destroy another writer's published generation
            self.store.delete(gen_key)
            raise PublishConflict(
                f"concurrent publish of {name!r} (lost the {version} "
                "manifest race)") from e
        return version

    def read_generation(self, name: str,
                        version: str | None = None) -> GenerationManifest:
        """Load the generation manifest for ``version`` (default: current)."""
        v = version if version is not None else self.current_version(name)
        d = StoreDirectory(self.store, self.version_prefix(name, v))
        return GenerationManifest.from_json(
            d.open_input(GENERATION_FILE).read_all())

    def current_generation(self, name: str) -> GenerationManifest:
        return self.read_generation(name)

    def publish_generation_state(self, name: str, gen: int, stats,
                                 vocab: dict,
                                 writer: dict | None = None) -> list:
        """Publish one generation's SHARED scoring state (live stats +
        vocab) as a segment; returns the ``stats_ref`` the partition
        manifests should carry. One copy per generation, however many
        partitions reference it.

        A :class:`~repro.index.builder.LiveStats` whose ``ref`` names a
        state it grew from is written as a delta of that state (see
        :class:`GenState`): what the commit changed, not the vocabulary.
        Anything else, or a chain ``STATE_SNAPSHOT_EVERY`` long, is written
        whole.

        ``writer`` optionally rides along as ``writer.json`` — coordinator
        bookkeeping (e.g. the round-robin placement cursor) a SECOND writer
        must adopt when it rebases on this generation, so a raced commit
        converges on the same document placement a serialized pair of
        commits would have produced."""
        seg = f"g{gen:06d}-state"
        delta = state = None
        parent = self._grown_from(name, stats)
        if parent is not None:
            delta = parent.delta_of(gen, seg, stats)
        if delta is not None:
            files = {STATE_DELTA_FILE: orjson.dumps(delta)}
            state = parent.child(seg, delta)
        else:
            whole = dict(stats)
            if hasattr(stats, "total_len"):
                whole["_total_len"] = stats.total_len
            files = {"stats.json": orjson.dumps(whole),
                     "vocab.json": orjson.dumps(vocab)}
        if writer is not None:
            files["writer.json"] = orjson.dumps(writer)
        self.publish_segment(name, seg, RamDirectory(files))
        if state is not None:
            self._remember((name, seg), state)
        return [name, seg]

    def _grown_from(self, name: str, stats) -> "GenState | None":
        ref = getattr(stats, "ref", None)
        if not ref or ref[0] != name:
            return None
        try:
            return self.generation_state(ref)
        except (NoSuchKey, KeyError, ValueError):
            return None

    def _remember(self, key: tuple, state: GenState) -> None:
        with self._states_lock:
            self._states[key] = state
            self._states.move_to_end(key)
            while len(self._states) > STATE_MEMO:
                self._states.popitem(last=False)

    def generation_state(self, stats_ref) -> GenState:
        """The resolved shared state a ``stats_ref`` names: from the
        catalog's memo, else read — a delta applied to its parent's
        resolution, a snapshot parsed whole."""
        key = (stats_ref[0], stats_ref[1])
        with self._states_lock:
            hit = self._states.get(key)
            if hit is not None:
                self._states.move_to_end(key)
                return hit
        d = self.open_segment(*key)
        if STATE_DELTA_FILE in d.list():
            delta = orjson.loads(d.open_input(STATE_DELTA_FILE).read_all())
            state = self.generation_state([key[0], delta["prev"]]).child(
                key[1], delta)
        else:
            stats = orjson.loads(d.open_input("stats.json").read_all())
            vocab = orjson.loads(d.open_input("vocab.json").read_all())
            gen = int(key[1][1:7]) if key[1][1:7].isdigit() else 0
            state = GenState.from_snapshot(gen, key[1], stats, vocab)
        self._remember(key, state)
        return state

    def resolve_generation_writer(self, manifest: GenerationManifest) -> dict:
        """The coordinator bookkeeping published with a generation's shared
        state ({} for inline-state or pre-writer-state generations)."""
        if manifest.stats_ref is None:
            return {}
        asset, seg = manifest.stats_ref
        d = self.open_segment(asset, seg)
        if "writer.json" not in d.list():
            return {}
        return orjson.loads(d.open_input("writer.json").read_all())

    def resolve_generation_state(self,
                                 manifest: GenerationManifest) -> tuple[dict, dict]:
        """(stats, vocab) for a manifest — inline, or read through the
        shared ``stats_ref`` segment (a billed store read)."""
        if manifest.stats is not None and manifest.vocab is not None:
            return manifest.stats, manifest.vocab
        if manifest.stats_ref is None:
            raise ValueError(
                f"generation {manifest.gen} manifest carries neither inline "
                "stats/vocab nor a stats_ref")
        state = self.generation_state(manifest.stats_ref)
        return state.as_stats(), state.vocab_view()

    # -- resolve (the serving side) ------------------------------------------------

    def current_version(self, name: str) -> str:
        data = self.store.get(self._manifest_key(name))
        return orjson.loads(data)["current"]

    def open(self, name: str, version: str | None = None, *,
             block_size: int = 1 << 20) -> tuple[str, StoreDirectory]:
        v = version if version is not None else self.current_version(name)
        return v, StoreDirectory(self.store, self.version_prefix(name, v),
                                 block_size=block_size)


def refresh_fleet(runtime, asset_name: str) -> int:
    """Invalidate `asset_name` in every instance's hydration cache. The next
    invocation per instance re-resolves the manifest and re-hydrates — a
    rolling, zero-downtime switch-over."""
    dropped = 0
    for inst in runtime._instances:
        dropped += inst.cache.invalidate(asset_name)
    return dropped


def rollover_fleet(runtime, fn_groups, gen: int, *,
                   ping_payload: dict | None = None,
                   t_arrival: float | None = None,
                   stagger: bool = True) -> list:
    """Swap every pool of every replica group to generation ``gen`` with
    zero downtime: ping each function ONCE with the new generation pinned
    in the payload (keepalive — billed to the idle line, excluded from
    latency percentiles and policy history), so every pool hydrates — and
    jit-specializes on — the new generation OFF the query path.

    Pools within one replica group roll over STAGGERED (``stagger=True``,
    the default): pool *r+1*'s pings dispatch at the instant pool *r*'s
    pings complete, so at most ONE of a group's pools is ever busy
    hydrating — a query landing mid-rollover always finds the group's
    other pools idle (already re-warmed, or still warm on the old
    generation, which stays readable until gc), instead of every pool
    going busy at the same instant and forcing the query to queue behind
    a hydration or cold-boot a fresh instance. Replica groups themselves
    roll in parallel at ``t_arrival`` — a query fans out to EVERY
    partition, so serializing across groups would stretch the rollover
    without sheltering anyone. A single-pool group (R=1) has nothing to
    stagger; its behaviour is bit-identical either way.

    In-flight queries are never dropped: a query dispatched before the
    swap carries its own pinned generation and any instance can still
    re-hydrate that older generation (old versions stay readable until
    gc), so the coordinator may flip its serving generation the moment
    these pings return. Retired/unregistered functions are skipped (a
    rollover racing a scale-down must not resurrect a draining pool).

    EVERY idle instance of a pool gets its own ping (concurrent pings at
    one arrival instant land on distinct instances): a pool grown to N by
    concurrent traffic would otherwise prewarm only its MRU instance and
    the other N-1 would hydrate the new generation IN-BAND on their next
    query — exactly the p99 spike the prewarm exists to prevent. Busy
    instances can't be prewarmed (FaaS can't interrupt a running
    invocation); they pay their re-hydration on first touch, like any
    cold start."""
    t0 = runtime.clock if t_arrival is None else t_arrival
    payload = dict(ping_payload or {})
    payload["gen"] = gen
    recs = []
    for group in fn_groups:
        t_pool = t0
        for fn in (group if isinstance(group, (list, tuple)) else [group]):
            if not runtime.registered(fn):
                continue
            idle = sum(1 for i in runtime._instances
                       if i.fn == fn and i.alive and i.busy_until <= t_pool)
            pool_recs = []
            for _ in range(max(1, idle)):
                _, rec = runtime.invoke(fn, dict(payload), t_arrival=t_pool,
                                        keepalive=True)
                pool_recs.append(rec)
            recs.extend(pool_recs)
            if stagger and pool_recs:
                t_pool = max(r.t_done for r in pool_recs)
    return recs
