"""Per-level checkpoint / resume (SURVEY.md §5.4).

The reference has no checkpointing — a solve is monolithic and in-memory.
For the north-star scale (4.5e12 states on a preemptible pod) restart-from-
level recovery is required. The unit of persistence is the natural unit of
the level-synchronous engine: one solved level = (sorted states, packed
value+remoteness cells via core.codec). Plain .npz per level plus a JSON
manifest — no framework dependency, shard-friendly, and the packed cell
format is exactly the HBM table layout.
"""

from __future__ import annotations

import json
import os
import pathlib

import jax.numpy as jnp
import numpy as np

from gamesmanmpi_tpu.compress import (
    CELL_CANDIDATES,
    DEFAULT_BLOCK_POSITIONS,
    GENERIC_CANDIDATES,
    KEY_CANDIDATES,
    encode_array,
)
from gamesmanmpi_tpu.core.codec import (
    pack_cells,
    unpack_cells,
    unpack_cells_np,
)
from gamesmanmpi_tpu.resilience import faults
# The sealed-read path (crc verify, torn-error tuple, the one np.load
# door) and the async engine live in store/ now; the names below are
# re-exports so every historical import site keeps working. ISSUE 11
# deleted the private copies — this module holds the npz FRAMING and
# the manifest/seal logic, the store holds the I/O.
from gamesmanmpi_tpu.store import (
    BLOCKS_META_MEMBER,
    CorruptSealError as CorruptCheckpointError,  # noqa: F401 - re-export
    TORN_SEAL_ERRORS as TORN_NPZ_ERRORS,
    default_store,
    file_crc32,
    file_key,
    loadz as _loadz,  # noqa: F401 - re-export (tests compare tables)
    read_npz_members,
)
from gamesmanmpi_tpu.utils.env import env_int, env_str


def _verify_enabled() -> bool:
    return env_str("GAMESMAN_CKPT_VERIFY", "1") not in (
        "0", "off", "false"
    )


def reshard_enabled() -> bool:
    """GAMESMAN_RESHARD (default on): may a resume adopt a checkpoint
    tree sealed at a DIFFERENT geometry (shard count, world size) by
    re-partitioning rows through the owner hash on load? Off pins
    resume to the sealed geometry — any mismatch raises
    :class:`CheckpointGeometryError` naming both geometries instead of
    silently adapting (or silently re-running forward from the root,
    the pre-elastic behavior)."""
    return env_str("GAMESMAN_RESHARD", "1") not in ("0", "off", "false")


class CheckpointGeometryError(ValueError):
    """A checkpoint tree's sealed geometry cannot (or — with
    GAMESMAN_RESHARD=0 — may not) serve the requested solve geometry.
    The message names the sealed vs requested (shards, world, epoch)
    so an operator never diagnoses an opaque resume abort."""


def repartition_rows(states, num_shards: int, *payloads):
    """Bucket one shard's rows by the owner hash at ``num_shards``.

    The elastic-resume primitive: ``states`` (any sorted or unsorted
    slice of the hash-partitioned space) splits into ``num_shards``
    buckets by the SAME splitmix64 owner hash the live solve routes
    with, and every ``payloads`` column stays row-aligned through the
    split. Returns ``[(states_t, *payloads_t) for t in range(S')]``
    with input row order preserved inside each bucket.
    """
    from gamesmanmpi_tpu.core.hashing import owner_shard_np

    states = np.asarray(states)
    payloads = tuple(np.asarray(p) for p in payloads)
    owners = owner_shard_np(states, num_shards)
    out = []
    for t in range(num_shards):
        sel = owners == t
        out.append((states[sel],) + tuple(p[sel] for p in payloads))
    return out


def reshard_shard_stream(load_shard, old_count: int, new_count: int):
    """Streamed shard-set re-partitioner: one sealed artifact set at S
    shards becomes per-shard arrays at S' shards.

    ``load_shard(s) -> (states, *payloads)`` pulls ONE old shard at a
    time (the callers pass the block-store-served sealed readers, so
    decoded-file residency is one old shard; the output — one level at
    the new geometry — is the caller's to hold, exactly what it was
    about to keep resident anyway). Rows bucket by the owner hash at
    ``new_count`` and each new shard's columns are sorted by state —
    the per-shard sorted invariant every consumer relies on. Payload
    columns stay row-aligned through both the partition and the sort.
    """
    frags: list = [[] for _ in range(new_count)]
    width = None
    for s in range(old_count):
        arrs = load_shard(s)
        if not isinstance(arrs, tuple):
            arrs = (arrs,)
        width = len(arrs)
        for t, part in enumerate(
            repartition_rows(arrs[0], new_count, *arrs[1:])
        ):
            frags[t].append(part)
    out = []
    for t in range(new_count):
        cols = [
            np.concatenate([f[i] for f in frags[t]])
            for i in range(width or 1)
        ]
        order = np.argsort(cols[0], kind="stable")
        out.append(tuple(c[order] for c in cols))
    return out


def _block_candidates(name: str, arr: np.ndarray):
    """Codec candidates by member shape (compress/codecs): sorted state
    arrays delta-code, packed uint32 cells split value/remoteness, and
    everything else (edge indices, slot maps) gets the DEFLATE backstop
    — raw passthrough always competes, so a pathological member can only
    tie, never lose."""
    if arr.dtype == np.uint32 and name.startswith("cells"):
        return CELL_CANDIDATES
    if arr.dtype.kind == "u":
        # states / frontier levels / keys: sorted by the engine's
        # invariants; keydelta declines gracefully if one is not.
        return KEY_CANDIDATES
    return GENERIC_CANDIDATES


def _savez(path, allow_block_framing=True, **arrays) -> tuple[int, int]:
    """Atomic npz write: tmp + os.replace. -> (raw bytes, stored bytes).

    allow_block_framing=False pins the PLAIN npz layout regardless of
    GAMESMAN_CKPT_COMPRESS=blocks: user-facing artifacts (``--table-out``
    tables via save_result_npz/save_table_npz) are consumed by plain
    np.load outside this repo, and a checkpoint knob must never silently
    change their format (framed members would read as uint8 bytes, not
    states). zip-level DEFLATE still applies — np.load understands it.

    Atomicity (ADVICE r5): resumed runs RE-save levels whose files already
    exist while the manifest still seals them — a death mid-overwrite
    would otherwise leave a sealed-but-truncated npz that kills the next
    resume with zipfile.BadZipFile instead of degrading to the intact
    prefix. The tmp name is per-writer (pid), same discipline as the
    manifest's.

    Compression (GAMESMAN_CKPT_COMPRESS):

    * ``auto`` (default) — np.savez_compressed below ~64 MB, raw npz
      above: small-game checkpoints stay tidy, big-run payloads write at
      disk speed (zlib over high-entropy packed bitboards costs
      ~50 MB/s/core for single-digit savings).
    * ``0``/``1`` — force raw / force zip-level DEFLATE.
    * ``blocks`` — the ISSUE 9 format: each 1-D member is framed into
      independently-decodable blocks (compress/blocks — keydelta for
      sorted states, cellpack for packed cells, raw when compression
      loses) inside an UNCOMPRESSED npz, with the per-member index in a
      ``__blocks__`` JSON member. Loaders go through :func:`_loadz`,
      which decodes transparently; a torn/bit-rotted block raises
      BlockCorruptError (a ValueError — already in TORN_NPZ_ERRORS), so
      the quarantine-and-degrade resume paths treat compressed
      corruption exactly like v1 torn files. Plain npz files keep
      loading regardless of the knob (resume across a flag flip works).
    """
    total = sum(a.nbytes for a in arrays.values())
    flag = env_str("GAMESMAN_CKPT_COMPRESS", "auto")
    if flag == "auto":
        compress = total < (64 << 20)
    else:
        compress = flag not in ("0", "off", "false")
    path = pathlib.Path(path)
    if path.suffix != ".npz":
        # np.savez appends .npz to extension-less paths; the atomic
        # tmp+replace write must keep that contract (`--table-out results`
        # has always produced results.npz — silently writing `results`
        # would leave a stale results.npz for consumers to read).
        path = path.with_name(path.name + ".npz")
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
    try:
        if flag == "blocks" and not allow_block_framing:
            compress = total < (64 << 20)  # the "auto" contract
        if flag == "blocks" and allow_block_framing:
            members, meta = {}, {}
            bp = env_int("GAMESMAN_DB_BLOCK", DEFAULT_BLOCK_POSITIONS)
            if bp <= 0:
                # Warn-and-default (the env-knob degradation contract):
                # a nonsensical block size must not kill a multi-hour
                # solve at its FIRST checkpoint seal. DbWriter validates
                # the same knob at construction; checkpoint writes have
                # no construction moment, so degrade here.
                import warnings

                warnings.warn(
                    f"GAMESMAN_DB_BLOCK={bp} is not positive; using "
                    f"{DEFAULT_BLOCK_POSITIONS}"
                )
                bp = DEFAULT_BLOCK_POSITIONS
            for name, a in arrays.items():
                arr = np.asarray(a)
                if arr.ndim != 1 or arr.dtype.hasobject:
                    members[name] = arr  # stored plain, absent from meta
                    continue
                index, blobs = encode_array(arr, bp, _block_candidates(
                    name, arr
                ))
                members[name] = np.frombuffer(
                    b"".join(blobs), dtype=np.uint8
                )
                meta[name] = index
            members[BLOCKS_META_MEMBER] = np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8
            )
            # Uncompressed zip: the payload is already entropy-coded
            # per block; zipping it again costs CPU for ~nothing.
            np.savez(tmp, **members)
        elif compress:
            np.savez_compressed(tmp, **arrays)
        else:
            np.savez(tmp, **arrays)
        stored = tmp.stat().st_size
        os.replace(tmp, path)
        return total, stored
    finally:
        tmp.unlink(missing_ok=True)


class LevelCheckpointer:
    """Saves solved levels as they complete; loads them for resume.

    All payload I/O routes through the block store (``store=``, default
    the process-wide :func:`default_store`): sealed reads go through the
    store's cache (so a level hinted by the solver's readahead is
    decoded before the solve thread asks), payload writes go
    write-behind (the solve thread never waits on DEFLATE+fsync), and
    every ``finish_*`` seal waits for its payload writes first — the
    GM8xx ordering invariant, chaos-verified at ``store.writebehind``.
    """

    def __init__(self, directory: str, store=None):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.manifest_path = self.dir / "manifest.json"
        self._store = store

    @property
    def store(self):
        """The block store serving this checkpointer (late-bound: the
        default store re-reads its env knobs, so a test flipping
        GAMESMAN_STORE_* between solves gets the fresh config)."""
        return self._store if self._store is not None else default_store()

    def flush_writes(self) -> None:
        """Barrier on pending write-behind payload writes (re-raising
        the first failure). Every seal path calls this unless its
        caller already waited on the specific tickets (the sharded
        solver's pipelined seals pass ``drain=False``)."""
        self.store.drain()

    # ------------------------------------------------------ sealed reads
    # The one read door (store.read over store/sealed.read_npz_members):
    # crc-verified, cache-served, prefetch-aware. Loaders are pure —
    # corruption discovered on a prefetch thread re-raises HERE, on the
    # consuming thread, where quarantine decisions live.

    def _npz_read_plan(self, path, names, manifest=None):
        """(key, loader) for one sealed npz payload — the SAME plan for
        hints and reads, so a hinted load is always a later cache hit."""
        want = None
        if _verify_enabled():
            if manifest is None:
                manifest = self.load_manifest()
            want = manifest.get("crc", {}).get(pathlib.Path(path).name)
        return file_key(path), (
            lambda: read_npz_members(path, names, crc=want)
        )

    def _read_npz(self, path, names, manifest=None):
        """Sealed members of one checkpoint npz, through the store."""
        key, loader = self._npz_read_plan(path, names, manifest)
        return self.store.read(key, loader)

    def _hint_npz(self, path, names, manifest=None) -> None:
        """Readahead hint for one sealed npz (decoded on the prefetch
        pool; a later _read_npz of the same unchanged file is a cache
        hit; an evicted or changed file degrades to a sync read)."""
        key, loader = self._npz_read_plan(path, names, manifest)
        self.store.hint(key, loader)

    def _level_path(self, level: int) -> pathlib.Path:
        return self.dir / f"level_{level:04d}.npz"

    def _write_manifest(self, manifest: dict) -> None:
        """Atomic replace, never truncate-in-place: under multi-host, only
        process 0 writes the manifest AFTER bind — but PEERS read it
        concurrently (completed_levels at backward start races the
        post-barrier seals), and bind_game itself writes from EVERY
        process at solve start. A torn read crashed a two-process run
        with JSONDecodeError (round 4); os.replace guarantees readers
        see old-or-new, never partial. The temp name is per-writer
        (pid): concurrent binders sharing one .tmp consumed each other's
        rename (FileNotFoundError — same lesson as the counts cache's
        private-per-writer tmp)."""
        tmp = self.manifest_path.with_suffix(f".json.{os.getpid()}.tmp")
        tmp.write_text(json.dumps(manifest))
        os.replace(tmp, self.manifest_path)

    # ------------------------------------------------------------ integrity
    # Per-file crc32, recorded in the manifest when a file is sealed and
    # verified when it is loaded for resume (store/sealed.verify_crc,
    # captured into each sealed-read plan). Atomic _savez already rules
    # out torn WRITES; the crc catches what atomicity cannot — silent
    # bit-rot, a partial overwrite by a foreign process, a filesystem
    # that lied about durability. A mismatching file is quarantined
    # (renamed .corrupt, unsealed from the manifest) by the CONSUMING
    # thread — the pure read may have run on a prefetch thread — and
    # the loader raises CorruptCheckpointError, which every
    # TORN_NPZ_ERRORS degrade path already turns into "recompute this
    # level from the intact prefix".

    def quarantine_level(self, level: int) -> None:
        """Rename a sealed level's file(s) to ``.corrupt`` and unseal it,
        so the run degrades to the intact prefix: the level recomputes
        (its frontier is still known) and re-seals over the quarantine.
        Idempotent — callers may race the loader's own quarantine."""
        self.flush_writes()  # never quarantine around an in-flight write
        manifest = self.load_manifest()
        paths = [self._level_path(level)]
        num = manifest.get("sharded_levels", {}).get(str(level))
        if num:
            paths += [self._shard_level_path(level, s) for s in range(num)]
        crc = manifest.get("crc", {})
        for p in paths:
            if p.exists():
                p.rename(p.with_name(p.name + ".corrupt"))
            crc.pop(p.name, None)
        if level in manifest.get("levels", []):
            manifest["levels"] = [
                l for l in manifest["levels"] if l != level
            ]
        manifest.get("sharded_levels", {}).pop(str(level), None)
        self._write_manifest(manifest)

    def quarantine_and_log(self, level: int, exc, logger=None) -> None:
        """The one degrade contract every resume path shares: quarantine
        the level's sealed files and emit the ``ckpt_degraded`` record
        (phase name + 200-char error truncation live HERE, not at three
        call sites)."""
        self.quarantine_level(level)
        if logger is not None:
            logger.log({
                "phase": "ckpt_degraded", "level": int(level),
                "error": str(exc)[:200],
            })

    def _quarantine_frontier(self, level: int) -> None:
        """Quarantine one incrementally-saved frontier level and truncate
        the discovery prefix there: every deeper frontier is unsealed too
        (the resume contract is contiguous-from-root), and the
        ``frontiers_complete`` flag drops so the engine re-expands from
        the surviving prefix instead of trusting a holed snapshot."""
        manifest = self.load_manifest()
        crc = manifest.get("crc", {})
        kept, dropped = [], []
        for k in manifest.get("forward_levels", []):
            (kept if int(k) < level else dropped).append(int(k))
        for k in dropped:
            p = self.dir / f"frontier_{k:04d}.npz"
            if k == level and p.exists():
                # Only the corrupt file is renamed; deeper levels are
                # merely unsealed (re-expansion re-saves over them).
                p.rename(p.with_name(p.name + ".corrupt"))
            crc.pop(p.name, None)
        manifest["forward_levels"] = sorted(kept)
        manifest.pop("frontiers_complete", None)
        self._write_manifest(manifest)

    # -------------------------------------------- cross-rank consistency
    # Multi-process seal stamps (ISSUE 6): each sealed artifact records
    # the run epoch it was taken in and which process rank owned each
    # shard file. Resume verifies every rank digests the SAME state
    # (ShardedSolver barriers on resume_digest) and can attribute a torn
    # or missing per-rank shard file to its writer instead of guessing.

    def stamp_run(self, num_processes: int, ranks=None) -> int:
        """Increment the manifest's run epoch (process 0, solve start).

        The epoch distinguishes seals taken by the current attempt from
        a previous (possibly differently-shaped) run's: a resumed solve
        after a rank death carries epoch N+1 while the surviving prefix
        keeps N — both valid, both loadable, but auditable."""
        manifest = self.load_manifest()
        run = manifest.get("run", {})
        epoch = int(run.get("epoch", 0)) + 1
        manifest["run"] = {
            "epoch": epoch,
            "num_processes": int(num_processes),
            "ranks": list(ranks) if ranks is not None else [],
        }
        self._write_manifest(manifest)
        return epoch

    def run_info(self) -> dict:
        """{"epoch", "num_processes", "ranks"} of the latest stamped run
        ({} for pre-distributed directories)."""
        return self.load_manifest().get("run", {})

    @staticmethod
    def _stamp_seal(manifest: dict, table: str, level: int,
                    ranks=None) -> None:
        """Record one seal's (epoch, rank-set) stamp in ``manifest``
        (caller writes the manifest — seal + stamp land atomically)."""
        manifest.setdefault(table, {})[str(level)] = {
            "epoch": int(manifest.get("run", {}).get("epoch", 0)),
            "ranks": list(ranks) if ranks is not None else [],
        }

    def resume_digest(self, num_shards: int) -> str:
        """Stable digest of everything resume decisions read: the
        deepest mutually-sealed solved level, the sealed level sets,
        the frontier snapshots, and the run epoch. Every rank computes
        it independently and barriers on it — agreement means the ranks
        share one view of the checkpoint directory; divergence aborts
        the fleet before any rank loads a different prefix.

        Geometry normalization (elastic resume): with GAMESMAN_RESHARD
        on (the default) the digest covers the DIRECTORY's sealed state
        only — the requested shard count drops out — so a W'-rank /
        S'-shard world can adopt a W-rank tree after the consistency
        barrier and reshard on load. With resharding pinned off the
        requested geometry stays in the digest (the legacy strict
        view)."""
        import hashlib

        manifest = self.load_manifest()
        completed = self.completed_levels()
        view = {
            "deepest_sealed": max(completed) if completed else None,
            "completed": completed,
            "sharded": sorted(manifest.get("sharded_levels", {})),
            "forward": sorted(manifest.get("forward_level_shards", {})),
            "frontier_shards": manifest.get("frontier_shards"),
            "frontiers": bool(manifest.get("frontiers")),
            "edges": sorted(manifest.get("edge_levels", {})),
            "epoch": manifest.get("run", {}).get("epoch", 0),
            "num_shards": None if reshard_enabled() else num_shards,
        }
        blob = json.dumps(view, sort_keys=True).encode()
        return hashlib.sha1(blob).hexdigest()

    def sealed_geometry(self, manifest=None) -> dict:
        """The geometry this tree's sealed shard artifacts were written
        at: ``{"shard_counts": sorted list of every sealed shard count
        (mixed trees happen mid-reshard), "num_shards": the single
        count or None when mixed/none, "num_processes": world size of
        the last stamped run (None pre-distributed), "epoch": run
        epoch}``. Global (non-shard) artifacts are geometry-free and do
        not participate; neither do sealed EDGE shards — their slot
        geometry never reshards (a foreign-count edge level takes the
        per-level lookup fallback structurally, pre-dating elasticity),
        so a stale consumed edge set must not hold the whole tree's
        geometry status hostage. This keeps the view in lockstep with
        the campaign's jax-free twin (``checkpoint_progress``)."""
        if manifest is None:
            manifest = self.load_manifest()
        counts = set()
        if manifest.get("frontier_shards"):
            counts.add(int(manifest["frontier_shards"]))
        for v in manifest.get("forward_level_shards", {}).values():
            counts.add(int(v))
        for v in manifest.get("sharded_levels", {}).values():
            counts.add(int(v))
        counts.discard(0)
        run = manifest.get("run", {})
        return {
            "shard_counts": sorted(counts),
            "num_shards": (
                next(iter(counts)) if len(counts) == 1 else None
            ),
            "num_processes": (
                int(run["num_processes"]) if "num_processes" in run
                else None
            ),
            "epoch": int(run.get("epoch", 0)),
        }

    def check_resume_geometry(self, num_shards: int,
                              num_processes: int = 1) -> dict:
        """The elastic-resume gate, called once at solve start: compare
        the sealed geometry against the requested one. Returns
        ``{"status": "fresh" | "match" | "reshard", "sealed": {...},
        "requested": {...}}`` — ``reshard`` means the loaders will
        re-partition rows on load (and sealed edge shards fall back to
        the per-level lookup backward). With GAMESMAN_RESHARD=0 any
        mismatch raises :class:`CheckpointGeometryError` NAMING both
        geometries — never an opaque abort, never a silent forward
        re-run."""
        sealed = self.sealed_geometry()
        requested = {
            "num_shards": int(num_shards),
            "num_processes": int(num_processes),
        }
        if not sealed["shard_counts"]:
            return {"status": "fresh", "sealed": sealed,
                    "requested": requested}
        shards_match = sealed["shard_counts"] == [int(num_shards)]
        world_match = sealed["num_processes"] in (None,
                                                 int(num_processes))
        if shards_match and world_match:
            return {"status": "match", "sealed": sealed,
                    "requested": requested}
        if not reshard_enabled():
            raise CheckpointGeometryError(
                f"checkpoint {self.dir} is sealed at "
                f"shards={sealed['shard_counts']} "
                f"world={sealed['num_processes']} "
                f"epoch={sealed['epoch']} but this solve requested "
                f"shards={num_shards} world={num_processes}, and "
                "GAMESMAN_RESHARD=0 pins resume to the sealed "
                "geometry — rerun with the sealed geometry, or unset "
                "GAMESMAN_RESHARD to reshard on load"
            )
        return {"status": "reshard", "sealed": sealed,
                "requested": requested}

    def bind_game(self, name: str) -> None:
        """Record/validate which game this directory belongs to.

        Game names encode every parameter (board, symmetry flag, ...), so a
        resume with a different spec — e.g. sym=1 against a sym=0 checkpoint,
        whose canonical tables would silently disagree — fails loudly here
        instead of mixing tables. Engines call this before loading anything.
        """
        manifest = self.load_manifest()
        bound = manifest.get("game")
        if bound is None:
            manifest["game"] = name
            self._write_manifest(manifest)
        elif bound != name:
            raise ValueError(
                f"checkpoint directory {self.dir} belongs to game {bound!r}, "
                f"not {name!r} — use a fresh --checkpoint-dir"
            )

    def save_level(self, level: int, table) -> None:
        cells = np.asarray(
            pack_cells(jnp.asarray(table.values), jnp.asarray(table.remoteness))
        )
        path = self._level_path(level)
        _savez(path, states=table.states, cells=cells)
        manifest = self.load_manifest()
        manifest["levels"] = sorted(set(manifest.get("levels", [])) | {level})
        # Seal + crc land in ONE manifest write: a death in between could
        # otherwise leave a sealed level whose crc is missing (fine — crc
        # checks are best-effort for pre-integrity files) but never a crc
        # for an unsealed level.
        manifest.setdefault("crc", {})[path.name] = file_crc32(path)
        self._write_manifest(manifest)
        faults.fire("ckpt.save_level", path=str(path), level=level)

    def load_manifest(self) -> dict:
        if self.manifest_path.exists():
            return json.loads(self.manifest_path.read_text())
        return {}

    def load_level(self, level: int):
        """Global (sorted) table of one level — from the global file, or
        assembled from per-shard files when the level was saved sharded.

        Verifies the manifest crc first; a mismatch quarantines the
        level and raises CorruptCheckpointError (a TORN_NPZ_ERRORS
        member), which resume paths degrade to a recompute."""
        from gamesmanmpi_tpu.solve.engine import LevelTable

        faults.fire("ckpt.load_level", level=level)
        path = self._level_path(level)
        if path.exists():
            try:
                states, cells = self._read_npz(path, ("states", "cells"))
            except CorruptCheckpointError:
                self.quarantine_level(level)
                raise
            values, remoteness = unpack_cells(jnp.asarray(cells))
            return LevelTable(
                states=states,
                values=np.asarray(values),
                remoteness=np.asarray(remoteness),
            )
        manifest = self.load_manifest()
        num = manifest.get("sharded_levels", {}).get(str(level))
        if num is None:
            raise FileNotFoundError(f"no checkpoint for level {level}")
        gs, gc = [], []
        for s in range(num):
            states, cells = self.load_level_shard(level, s, manifest)
            gs.append(states)
            gc.append(cells)
        states = np.concatenate(gs)
        cells = np.concatenate(gc)
        order = np.argsort(states)
        values, remoteness = unpack_cells_np(cells[order])
        return LevelTable(
            states=states[order], values=values, remoteness=remoteness
        )

    def completed_levels(self) -> list[int]:
        manifest = self.load_manifest()
        levels = set(manifest.get("levels", []))
        levels |= {int(k) for k in manifest.get("sharded_levels", {})}
        return sorted(levels)

    # ---------------------------------------------------- dense (per-level)
    # The dense engine's unit of persistence is one level's flat u8 cell
    # array (its entire state — no frontiers exist). The backward sweep
    # chains deepest-first, so only a CONTIGUOUS completed prefix from the
    # top is resumable; the engine computes that prefix itself.

    def save_dense_level(self, level: int, cells) -> None:
        _savez(self.dir / f"dense_{level:04d}.npz",
               cells=np.asarray(cells).reshape(-1))
        manifest = self.load_manifest()
        manifest["dense_levels"] = sorted(
            set(manifest.get("dense_levels", [])) | {level}
        )
        self._write_manifest(manifest)

    def dense_levels(self) -> list:
        return sorted(self.load_manifest().get("dense_levels", []))

    def load_dense_level(self, level: int) -> np.ndarray:
        (cells,) = self._read_npz(self.dir / f"dense_{level:04d}.npz",
                                  ("cells",))
        return cells

    # ------------------------------------------------- sharded (per-shard)
    # One file per (level, shard) and per (frontier snapshot, shard): no
    # global array is ever assembled on one host to WRITE a checkpoint —
    # the single-host-TB bottleneck VERDICT r2 flagged. Multi-host: each
    # process saves only the shards it owns; `finish_*` records the shard
    # count once the set is complete.

    def _shard_level_path(self, level: int, shard: int) -> pathlib.Path:
        return self.dir / f"level_{level:04d}.shard_{shard:04d}.npz"

    def _savez_behind(self, path, **arrays):
        """Write-behind _savez: enqueue the DEFLATE+tmp+os.replace on
        the store's ordered worker and return the WriteTicket (resolved
        to the (raw, stored) byte pair). Arrays are materialized HERE,
        on the calling thread — device downloads must not happen on the
        writer. With write-behind off the write runs inline and the
        ticket is already resolved — callers are agnostic."""
        arrays = {k: np.asarray(v) for k, v in arrays.items()}

        def job(path=path, arrays=arrays):
            return _savez(path, **arrays)

        return self.store.write(job, path=str(path))

    def save_level_shard(self, level: int, shard: int, states, cells):
        """-> WriteTicket resolving to (raw, stored) bytes — the sharded
        engine folds them into its ckpt_bytes_* stats (after the seal
        waits on the ticket) so an operator can see what the
        spill/checkpoint tier costs (and what ``blocks`` compression
        saves) without stat-ing the directory."""
        return self._savez_behind(
            self._shard_level_path(level, shard), states=states, cells=cells
        )

    def finish_level_shards(self, level: int, num_shards: int,
                            ranks=None, drain: bool = True) -> None:
        """Seal one level's shard set. ``drain=False`` is for callers
        that already waited on this level's write tickets (the sharded
        solver's pipelined seals) — a global drain there would stall on
        NEWER levels' queued payloads and collapse the pipeline."""
        if drain:
            self.flush_writes()
        manifest = self.load_manifest()
        manifest.setdefault("sharded_levels", {})[str(level)] = num_shards
        # The sealer (process 0, post-barrier) records every shard file's
        # crc — the files live on the shared checkpoint filesystem, and
        # sealing is the one moment the set is known complete.
        crc = manifest.setdefault("crc", {})
        for s in range(num_shards):
            p = self._shard_level_path(level, s)
            if p.exists():
                crc[p.name] = file_crc32(p)
        self._stamp_seal(manifest, "level_seals", level, ranks)
        self._write_manifest(manifest)
        faults.fire(
            "ckpt.save_level",
            path=str(self._shard_level_path(level, 0)),
            level=level,
        )

    def level_shard_count(self, level: int):
        """Shards the level was saved with, or None if not saved sharded."""
        return self.load_manifest().get("sharded_levels", {}).get(str(level))

    def load_level_shard(self, level: int, shard: int, manifest=None):
        """-> (states, packed cells) of one shard of one level (crc-
        verified; a mismatch quarantines the whole level and raises).
        Callers looping over a level's shards pass one pre-loaded
        ``manifest`` instead of paying a read per shard."""
        path = self._shard_level_path(level, shard)
        try:
            return self._read_npz(path, ("states", "cells"), manifest)
        except CorruptCheckpointError:
            self.quarantine_level(level)
            raise

    def prefetch_level_shards(self, level: int, num_shards: int,
                              manifest=None) -> None:
        """Readahead hint for one sealed level's shard files (the
        solver's level schedule calls this one level AHEAD of the
        backward resolve that will load them)."""
        if manifest is None:
            manifest = self.load_manifest()
        for s in range(num_shards):
            self._hint_npz(self._shard_level_path(level, s),
                           ("states", "cells"), manifest)

    def prefetch_level(self, level: int) -> None:
        """Readahead hint for one sealed GLOBAL level file."""
        path = self._level_path(level)
        if path.exists():
            self._hint_npz(path, ("states", "cells"))

    def lookup_level_state(self, level: int, state):
        """(value, remoteness) of one CANONICAL packed state, served from
        this directory's files — or None when the level/state is absent.

        The big-run query path (SURVEY.md §1: every reachable position is a
        by-product of the solve): with store_tables=False nothing lives in
        host memory, but the checkpoint holds every solved cell. Reads the
        global level file when present; otherwise exactly ONE
        (level, shard) file, chosen by the same owner hash that routed the
        state during the solve — never assembles the level.
        """
        cache = getattr(self, "_lookup_cache", None)
        path = self._level_path(level)
        if path.exists():
            cache_key = (level, None)
        else:
            num = self.level_shard_count(level)
            if num is None:
                return None
            from gamesmanmpi_tpu.core.hashing import owner_shard_np

            shard = int(owner_shard_np(
                np.asarray([state], dtype=np.uint64), num
            )[0])
            cache_key = (level, shard)
        if cache is not None and cache[0] == cache_key:
            states, cells = cache[1]
        elif cache_key[1] is None:
            states, cells = self._read_npz(path, ("states", "cells"))
        else:
            states, cells = self.load_level_shard(level, cache_key[1])
        # Memoize the last-loaded table: a batch of point queries often
        # lands in the same (level, shard), and at big-run scale one shard
        # file is a multi-hundred-MB read.
        self._lookup_cache = (cache_key, (states, cells))
        # Per-shard slices keep the engine's sorted invariant; the global
        # file is sorted by construction. The probe is the shared
        # canonicalize→probe search every query route uses (core/probe.py).
        from gamesmanmpi_tpu.core.probe import probe_sorted_np

        idx, hit = probe_sorted_np(
            states, np.asarray([state], dtype=states.dtype)
        )
        if not hit[0]:
            return None
        values, remoteness = unpack_cells_np(cells[idx[0] : idx[0] + 1])
        return int(values[0]), int(remoteness[0])

    # ------------------------------------------------ edges (per-shard)
    # The sharded engine's forward edge provenance (ISSUE 3): one npz per
    # (level, shard) holding that shard's edge-index row (eidx — each
    # child's unique-index within its owner's next-level slice, in routing
    # order) and its reply-slot map (slot). Sealed with the geometry the
    # backward must validate on resume: shard count, routing capacity
    # (ecap) and slot length (level capacity x max_moves). A level absent
    # here simply falls back to the lookup backward — pre-edge checkpoint
    # directories keep resuming unchanged.

    def _edges_path(self, level: int, shard: int) -> pathlib.Path:
        return self.dir / f"edges_{level:04d}.shard_{shard:04d}.npz"

    def save_edges_shard(self, level: int, shard: int, eidx, slot):
        """-> WriteTicket resolving to (raw, stored) bytes, like
        save_level_shard."""
        return self._savez_behind(
            self._edges_path(level, shard),
            eidx=np.asarray(eidx, dtype=np.int32),
            slot=np.asarray(slot, dtype=np.int32),
        )

    def finish_edges_level(self, level: int, num_shards: int, ecap: int,
                           slot_len: int, ranks=None,
                           drain: bool = True) -> None:
        """Seal one level's edge-shard set (process 0, post-barrier)."""
        if drain:
            self.flush_writes()
        manifest = self.load_manifest()
        manifest.setdefault("edge_levels", {})[str(level)] = {
            "shards": num_shards, "ecap": int(ecap),
            "slot_len": int(slot_len),
        }
        self._stamp_seal(manifest, "edge_seals", level, ranks)
        self._write_manifest(manifest)

    def edge_level_info(self, level: int):
        """{"shards", "ecap", "slot_len"} of a sealed edge level, or None."""
        return self.load_manifest().get("edge_levels", {}).get(str(level))

    def load_edges_shard(self, level: int, shard: int, manifest=None):
        """-> (eidx [S*ecap] int32, slot [cap*M] int32) of one shard.
        Callers looping over a level's shards pass one pre-loaded
        ``manifest`` instead of paying a read per shard."""
        return self._read_npz(self._edges_path(level, shard),
                              ("eidx", "slot"), manifest)

    def prefetch_edges_level(self, level: int, num_shards: int,
                             manifest=None) -> None:
        """Readahead hint for one sealed level's edge-shard files (the
        backward schedule hints level N-1's edges while level N
        resolves — today's synchronous disk-spilled edge loads become
        cache hits). Pass the already-loaded ``manifest``: S redundant
        manifest reads per hinted level on a shared checkpoint
        filesystem would pay back part of the overlap win."""
        if manifest is None:
            manifest = self.load_manifest()
        for s in range(num_shards):
            self._hint_npz(self._edges_path(level, s), ("eidx", "slot"),
                           manifest)

    # Incremental per-(level, shard) forward saves — the sharded analog of
    # save_frontier_level: written as each level is discovered, superseded
    # by the consolidated per-shard snapshot once forward completes (the
    # format load_frontier_shards/load_frontiers already resume from, which
    # also supports shard-count changes), then deleted.

    def save_forward_level_shard(self, level: int, shard: int, states):
        """-> WriteTicket resolving to (raw, stored) bytes, like
        save_level_shard."""
        return self._savez_behind(
            self.dir / f"frontier_{level:04d}.shard_{shard:04d}.npz",
            states=np.asarray(states),
        )

    def finish_forward_level(self, level: int, num_shards: int,
                             ranks=None, drain: bool = True) -> None:
        """Seal one forward level's shard set (process 0, post-barrier —
        same write discipline as finish_level_shards, including the
        per-file crc so a torn per-rank frontier file is caught and
        quarantined on resume rather than silently resuming a holed
        discovery prefix)."""
        if drain:
            self.flush_writes()
        manifest = self.load_manifest()
        manifest.setdefault("forward_level_shards", {})[str(level)] = (
            num_shards
        )
        crc = manifest.setdefault("crc", {})
        for s in range(num_shards):
            p = self.dir / f"frontier_{level:04d}.shard_{s:04d}.npz"
            if p.exists():
                crc[p.name] = file_crc32(p)
        self._stamp_seal(manifest, "forward_seals", level, ranks)
        self._write_manifest(manifest)

    def _quarantine_forward_shard_level(self, level: int,
                                        num_shards: int) -> None:
        """Quarantine one sealed forward level's shard files and unseal
        it together with every deeper forward level (the resume contract
        is contiguous-from-root): the run degrades to the longest
        rank-consistent prefix and re-expands from its deepest level.

        Each dropped level's files are enumerated at ITS OWN sealed
        shard count (``num_shards`` is only the fallback for records
        missing one) — a mid-reshard tree legitimately seals adjacent
        levels at different counts.

        Idempotent and concurrency-tolerant: under multi-process resume
        EVERY rank walks the same torn directory (the resume-digest
        barrier runs before loads, but the tear itself is discovered
        during them), so a peer may rename a file between this rank's
        exists() and rename() — losing that race is fine (the file IS
        quarantined), and the manifest rewrite is atomic with identical
        content on every rank."""
        manifest = self.load_manifest()
        crc = manifest.get("crc", {})
        rec = manifest.get("forward_level_shards", {})
        dropped = [k for k in rec if int(k) >= level]
        for k in dropped:
            sealed_count = int(rec.get(k) or num_shards)
            rec.pop(k, None)
            manifest.get("forward_seals", {}).pop(k, None)
            for s in range(sealed_count):
                p = self.dir / f"frontier_{int(k):04d}.shard_{s:04d}.npz"
                if int(k) == level and p.exists():
                    try:
                        p.rename(p.with_name(p.name + ".corrupt"))
                    except OSError:
                        pass  # a peer rank won the rename race
                crc.pop(p.name, None)
        self._write_manifest(manifest)

    def load_forward_level_shards(self, num_shards: int) -> dict:
        """-> {level: [per-shard arrays at ``num_shards``]} of every
        sealed forward level, a (possibly partial) discovery prefix; {}
        when none exist.

        Elastic resume (ISSUE 13): a level sealed at a DIFFERENT shard
        count re-partitions through the owner hash on load (streamed —
        one sealed shard file decoded at a time through the block
        store), per level, so a mid-reshard tree with mixed counts
        resumes too. With GAMESMAN_RESHARD=0 a mismatched level raises
        :class:`CheckpointGeometryError` naming the sealed vs requested
        geometry (the pre-elastic behavior silently re-ran forward from
        the root — an opaque loss of hours at big-run scale)."""
        manifest = self.load_manifest()
        rec = manifest.get("forward_level_shards", {})
        out: dict = {}
        mismatched = sorted(
            {int(rec[k]) for k in rec if int(rec[k]) != num_shards}
        )
        if mismatched and not reshard_enabled():
            geom = self.sealed_geometry(manifest)
            raise CheckpointGeometryError(
                f"forward checkpoint levels in {self.dir} are sealed at "
                f"shards={mismatched} (epoch {geom['epoch']}) but this "
                f"solve requested shards={num_shards}, and "
                "GAMESMAN_RESHARD=0 pins resume to the sealed geometry"
            )
        # Batched readahead over the WHOLE prefix before the first read:
        # resume loads are the serial head of a solve, and the prefetch
        # pool decodes level j+1's shards while level j's arrays are
        # consumed. Hints follow each level's OWN sealed count.
        for k in sorted(rec, key=int):
            for s in range(int(rec[k])):
                self._hint_npz(
                    self.dir / f"frontier_{int(k):04d}.shard_{s:04d}.npz",
                    ("states",), manifest,
                )
        # Levels in ascending order: the consumer (_forward_fast) resumes
        # only a contiguous-from-root prefix, so a torn level truncates
        # there — everything below it is still a valid (shorter) resume.
        for k in sorted(rec, key=int):
            sealed_count = int(rec[k])

            def _one(s, k=k):
                path = self.dir / (
                    f"frontier_{int(k):04d}.shard_{s:04d}.npz"
                )
                (states,) = self._read_npz(path, ("states",), manifest)
                return states

            try:
                if sealed_count == num_shards:
                    arrs = [_one(s) for s in range(num_shards)]
                else:
                    arrs = [
                        part[0] for part in reshard_shard_stream(
                            _one, sealed_count, num_shards
                        )
                    ]
            except TORN_NPZ_ERRORS:
                # Torn or crc-mismatching per-rank file (a death between
                # unlink and manifest write in an older layout, a
                # mid-resave before _savez became atomic, or a rank's
                # write the filesystem lied about — BadZipFile/short-read
                # OSError/KeyError/CorruptCheckpointError, ADVICE r5):
                # quarantine this level and keep the intact prefix below
                # it — at big-run scale the prefix is hours of
                # re-discovery — and re-run forward from its deepest.
                self._quarantine_forward_shard_level(int(k), sealed_count)
                break
            out[int(k)] = arrs
        return out

    def drop_forward_level_shards(self) -> None:
        """Forward completed and the consolidated snapshot is sealed: the
        incremental files are now redundant on disk (at big-run scale the
        frontier set is the largest artifact — keep exactly one copy)."""
        manifest = self.load_manifest()
        dropped = manifest.pop("forward_level_shards", {})
        # Manifest first, unlinks second: a death in between leaves orphan
        # files (harmless) instead of sealed entries pointing at deleted
        # files (a FileNotFoundError trap for any future loader).
        self._write_manifest(manifest)
        for k in dropped:
            for path in self.dir.glob(
                f"frontier_{int(k):04d}.shard_*.npz"
            ):
                path.unlink(missing_ok=True)

    def save_frontier_shard(self, shard: int, pools):
        """One shard's slice of every frontier level, one file.
        -> WriteTicket (write-behind, like save_level_shard)."""
        arrays = {
            f"level_{k:04d}": np.asarray(v) for k, v in pools.items()
        }
        return self._savez_behind(
            self.dir / f"frontiers.shard_{shard:04d}.npz", **arrays
        )

    def finish_frontier_shards(self, num_shards: int,
                               drain: bool = True) -> None:
        if drain:
            self.flush_writes()
        manifest = self.load_manifest()
        manifest["frontier_shards"] = num_shards
        self._write_manifest(manifest)

    def load_frontier_shards(self, num_shards: int):
        """-> {level: [per-shard arrays at ``num_shards``]} from the
        consolidated per-shard snapshot, or None when no snapshot
        exists (caller falls back to load_frontiers).

        Elastic resume: a snapshot sealed at a different shard count
        re-partitions on load — STREAMED, one sealed shard file (all
        its levels) decoded at a time through the block store, never a
        global frontier assembly (the single-host-TB bottleneck the
        per-shard layout exists to avoid). With GAMESMAN_RESHARD=0 a
        mismatch raises :class:`CheckpointGeometryError` naming both
        geometries."""
        manifest = self.load_manifest()
        sealed_count = manifest.get("frontier_shards")
        if sealed_count is None:
            return None
        sealed_count = int(sealed_count)
        if sealed_count != num_shards and not reshard_enabled():
            geom = self.sealed_geometry(manifest)
            raise CheckpointGeometryError(
                f"frontier snapshot in {self.dir} is sealed at "
                f"shards={sealed_count} (epoch {geom['epoch']}) but "
                f"this solve requested shards={num_shards}, and "
                "GAMESMAN_RESHARD=0 pins resume to the sealed geometry"
            )
        paths = [self.dir / f"frontiers.shard_{s:04d}.npz"
                 for s in range(sealed_count)]
        for path in paths:  # batched readahead before the first read
            self._hint_npz(path, None, manifest)
        if sealed_count == num_shards:
            out: dict = {}
            for s, path in enumerate(paths):
                members = self._read_npz(path, None, manifest)
                for name, arr in members.items():
                    k = int(name.split("_")[1])
                    out.setdefault(k, [None] * num_shards)[s] = arr
            return out
        # Reshard-on-resume: bucket each old shard's per-level rows by
        # the owner hash at the new count, then sort each new shard's
        # concatenated fragments (per-shard sorted is the engine
        # invariant; fragments are disjoint, so the sort is a merge).
        frags: dict = {}
        for path in paths:
            members = self._read_npz(path, None, manifest)
            for name, arr in members.items():
                k = int(name.split("_")[1])
                tgt = frags.setdefault(k, [[] for _ in range(num_shards)])
                for t, (part,) in enumerate(
                    repartition_rows(arr, num_shards)
                ):
                    tgt[t].append(part)
        return {
            k: [np.sort(np.concatenate(f)) for f in per_new]
            for k, per_new in frags.items()
        }

    # ------------------------------------------- disk budget (ISSUE 12)
    # The campaign regime's third failure class is disk exhaustion: at
    # 7x6 scale the checkpoint tree is the largest thing on the volume,
    # and a multi-day run accretes superseded artifacts — quarantined
    # .corrupt files, per-writer .tmp strays from deaths, unsealed shard
    # files resume ignores, and edge shards whose level has already been
    # resolved AND sealed (the backward's structural per-level fallback
    # to the lookup join makes deleting them safe). disk_usage() feeds
    # the gamesman_ckpt_bytes{kind} gauges; gc_superseded() reclaims the
    # superseded classes so ENOSPC becomes pause -> GC -> retry
    # (resilience/campaign.py) instead of a dead campaign.

    #: filename-prefix -> kind for the disk gauges and the GC scan.
    _KIND_PREFIXES = (
        ("level_", "level"),
        ("frontier", "frontier"),  # frontier_*, frontiers.npz, shards
        ("edges_", "edges"),
        ("dense_", "dense"),
    )

    @classmethod
    def artifact_kind(cls, name: str) -> str:
        """Classify one checkpoint-tree filename for the disk gauges:
        ``corrupt`` and ``tmp`` beat the payload prefixes (a quarantined
        level is reclaimable, a sealed one is not)."""
        if name == "manifest.json":
            return "manifest"
        if name.endswith(".corrupt"):
            return "corrupt"
        if ".tmp" in name:
            return "tmp"
        for prefix, kind in cls._KIND_PREFIXES:
            if name.startswith(prefix):
                return kind
        return "other"

    def disk_usage(self, registry=None) -> dict:
        """Bytes on disk per artifact kind, published as the
        ``gamesman_ckpt_bytes{kind=...}`` gauges (every kind is always
        set, so a GC'd kind reads 0 instead of a stale gauge)."""
        usage = {kind: 0 for _, kind in self._KIND_PREFIXES}
        usage.update({"manifest": 0, "corrupt": 0, "tmp": 0, "other": 0})
        try:
            entries = list(os.scandir(self.dir))
        except OSError:
            entries = []
        for entry in entries:
            try:
                if not entry.is_file():
                    continue
                usage[self.artifact_kind(entry.name)] += (
                    entry.stat().st_size
                )
            except OSError:
                continue  # racing unlink (another rank's quarantine)
        if registry is None:
            from gamesmanmpi_tpu.obs import default_registry

            registry = default_registry()
        for kind, nbytes in usage.items():
            registry.gauge(
                "gamesman_ckpt_bytes",
                "checkpoint-tree bytes on disk by artifact kind",
                kind=kind,
            ).set(float(nbytes))
        return usage

    def quarantine_inventory(self) -> list:
        """[{"file", "bytes"}] of quarantined ``.corrupt`` artifacts —
        the campaign's diagnosis bundle snapshots this BEFORE a GC
        deletes the evidence."""
        out = []
        for p in sorted(self.dir.glob("*.corrupt")):
            try:
                out.append({"file": p.name, "bytes": p.stat().st_size})
            except OSError:
                continue
        return out

    def referenced_files(self, manifest=None) -> set:
        """Filenames the manifest currently seals (the NOT-superseded
        set). Anything else matching an artifact prefix is a stray a
        death left behind — resume already ignores it on disk, GC may
        reclaim it."""
        if manifest is None:
            manifest = self.load_manifest()
        ref = {"manifest.json"}
        for k in manifest.get("levels", []):
            ref.add(f"level_{int(k):04d}.npz")
        for k, num in manifest.get("sharded_levels", {}).items():
            for s in range(int(num)):
                ref.add(f"level_{int(k):04d}.shard_{s:04d}.npz")
        for k in manifest.get("forward_levels", []):
            ref.add(f"frontier_{int(k):04d}.npz")
        for k, num in manifest.get("forward_level_shards", {}).items():
            for s in range(int(num)):
                ref.add(f"frontier_{int(k):04d}.shard_{s:04d}.npz")
        if manifest.get("frontiers"):
            ref.add("frontiers.npz")
        for s in range(int(manifest.get("frontier_shards") or 0)):
            ref.add(f"frontiers.shard_{s:04d}.npz")
        for k, info in manifest.get("edge_levels", {}).items():
            for s in range(int(info.get("shards", 0))):
                ref.add(f"edges_{int(k):04d}.shard_{s:04d}.npz")
        for k in manifest.get("dense_levels", []):
            ref.add(f"dense_{int(k):04d}.npz")
        return ref

    def gc_superseded(self, logger=None, registry=None) -> dict:
        """Reclaim superseded checkpoint artifacts; -> {"files",
        "bytes", "kinds": {kind: bytes}}.

        Reclaimed classes, in order:

        * **consumed edges** — edge shards of levels sealed solved: the
          backward that needed them already ran, and a future resume of
          a re-quarantined level falls back to the lookup join (the
          structural per-level fallback), so these are pure cache. The
          manifest unseals them FIRST, files unlink second — a death in
          between leaves orphans the next GC collects, never sealed
          entries pointing at deleted files;
        * **quarantine** — ``.corrupt`` files (superseded the moment the
          level re-sealed over them; snapshot quarantine_inventory()
          first if the forensics matter);
        * **tmp strays** — dead writers' per-pid temp files;
        * **unreferenced artifacts** — level/frontier/edge/dense files
          the manifest does not seal (unsealed write-behind strays,
          post-consolidation orphans).

        Contract: a QUIESCENT tree — call between attempts (the
        campaign supervisor's use) or from the solve thread of the only
        live solver. The write-behind queue is drained first so an
        in-flight payload whose seal has not run yet is never read as a
        stray mid-write (the store-ticket/seal-ordering invariant).
        """
        self.flush_writes()
        manifest = self.load_manifest()
        solved = set(int(k) for k in manifest.get("levels", []))
        solved |= {int(k) for k in manifest.get("sharded_levels", {})}
        consumed = {
            k: int(info.get("shards", 0))
            for k, info in manifest.get("edge_levels", {}).items()
            if int(k) in solved
        }
        if consumed:
            for k in consumed:
                manifest.get("edge_levels", {}).pop(k, None)
                manifest.get("edge_seals", {}).pop(k, None)
            self._write_manifest(manifest)
        freed = {"files": 0, "bytes": 0, "kinds": {}}

        def reclaim(path: pathlib.Path, kind: str) -> None:
            try:
                nbytes = path.stat().st_size
                path.unlink()
            except OSError:
                return  # racing unlink / already gone
            freed["files"] += 1
            freed["bytes"] += nbytes
            freed["kinds"][kind] = freed["kinds"].get(kind, 0) + nbytes

        for k, shards in consumed.items():
            for s in range(shards):
                reclaim(self._edges_path(int(k), s), "edges")
        referenced = self.referenced_files(manifest)
        for p in sorted(self.dir.iterdir()):
            if not p.is_file() or p.name in referenced:
                continue
            kind = self.artifact_kind(p.name)
            if kind != "other":  # unknown files are never GC fodder
                reclaim(p, kind)
        if registry is None:
            from gamesmanmpi_tpu.obs import default_registry

            registry = default_registry()
        registry.counter(
            "gamesman_ckpt_gc_reclaimed_bytes_total",
            "checkpoint bytes reclaimed by retention GC",
        ).inc(float(freed["bytes"]))
        if logger is not None:
            logger.log({"phase": "ckpt_gc", **{
                k: v for k, v in freed.items() if k != "kinds"
            }, "kinds": dict(freed["kinds"])})
        self.disk_usage(registry=registry)  # refresh the gauges post-GC
        return freed

    # Forward-phase snapshot: all per-level frontiers after discovery, so a
    # restarted solve skips the whole forward sweep (restart-from-level,
    # SURVEY.md §5.4 — the backward phase then loads completed levels).
    #
    # Two granularities. The original all-at-once snapshot (save_frontiers)
    # only helps once forward COMPLETES; at big-board scale forward alone is
    # a multi-hour phase that can outlast the host or device it runs on
    # (docs/ARCHITECTURE.md "6x6 single-chip feasibility"), so the fast-path
    # engine saves each level INCREMENTALLY as it is discovered
    # (save_frontier_level) and marks completion with a manifest flag — same
    # total bytes as the end snapshot, but a mid-forward death keeps the
    # discovered prefix and the next run resumes expansion from the deepest
    # saved level instead of restarting discovery from the root.

    def save_frontier_level(self, level: int, states) -> None:
        """One discovered level's frontier, saved the moment its count is
        known. The manifest records the level only after the file is fully
        written, so a death mid-write never yields a listed-but-corrupt
        entry (same discipline as save_level)."""
        path = self.dir / f"frontier_{level:04d}.npz"
        _savez(path, states=np.asarray(states))
        manifest = self.load_manifest()
        manifest["forward_levels"] = sorted(
            set(manifest.get("forward_levels", [])) | {level}
        )
        manifest.setdefault("crc", {})[path.name] = file_crc32(path)
        self._write_manifest(manifest)
        faults.fire("ckpt.save_frontier", path=str(path), level=level)

    def load_forward_levels(self) -> dict:
        """-> {level: sorted packed states} saved incrementally during a
        (possibly interrupted) forward sweep; {} when none exist. A
        torn or crc-mismatching level quarantines there and keeps the
        intact prefix below it (re-expansion resumes from its deepest),
        exactly like the sharded loader's torn-directory handling."""
        out = {}
        manifest = self.load_manifest()
        ks = sorted(manifest.get("forward_levels", []), key=int)
        for k in ks:  # batched readahead before the first read
            self._hint_npz(self.dir / f"frontier_{int(k):04d}.npz",
                           ("states",), manifest)
        for k in ks:
            path = self.dir / f"frontier_{int(k):04d}.npz"
            try:
                (out[int(k)],) = self._read_npz(path, ("states",),
                                                manifest)
            except TORN_NPZ_ERRORS:
                out.pop(int(k), None)
                self._quarantine_frontier(int(k))
                break
        return out

    def mark_frontiers_complete(self) -> None:
        """Forward discovery finished; every level is on disk via
        save_frontier_level. load_frontiers then serves resumes from the
        per-level files — no end-of-forward re-snapshot."""
        manifest = self.load_manifest()
        manifest["frontiers_complete"] = True
        self._write_manifest(manifest)

    def save_frontiers(self, pools) -> None:
        # Frontiers keep the game's state dtype (uint32 games stay uint32 —
        # at north-star scale the snapshot is the biggest file on disk).
        arrays = {
            f"level_{k:04d}": np.asarray(v) for k, v in pools.items()
        }
        path = self.dir / "frontiers.npz"
        _savez(path, **arrays)
        manifest = self.load_manifest()
        manifest["frontiers"] = True
        manifest.setdefault("crc", {})[path.name] = file_crc32(path)
        self._write_manifest(manifest)
        faults.fire("ckpt.save_frontier", path=str(path))

    def load_frontiers(self):
        """-> {level: sorted packed states} or None if no snapshot exists.

        Reads the global snapshot, or assembles one from per-shard snapshot
        files (a sharded run's checkpoint resumed at a different shard
        count, or by the single-device solver).
        """
        manifest = self.load_manifest()
        if manifest.get("frontiers"):
            path = self.dir / "frontiers.npz"
            if path.exists():
                try:
                    members = self._read_npz(path, None, manifest)
                    return {
                        int(name.split("_")[1]): arr
                        for name, arr in members.items()
                    }
                except TORN_NPZ_ERRORS:
                    # Corrupt global snapshot: quarantine it and fall
                    # through to the other resume sources (or a fresh
                    # forward) instead of dying on resume.
                    path.rename(path.with_name(path.name + ".corrupt"))
                    manifest.pop("frontiers", None)
                    manifest.get("crc", {}).pop(path.name, None)
                    self._write_manifest(manifest)
        if manifest.get("frontiers_complete"):
            out = self.load_forward_levels()
            if self.load_manifest().get("frontiers_complete"):
                return out
            # A frontier level quarantined mid-load: the snapshot is no
            # longer complete — resume as a partial forward instead
            # (load_forward_levels serves the intact prefix).
            return None
        num = manifest.get("frontier_shards")
        if num is None:
            return None
        shards = self.load_frontier_shards(num)
        return {
            k: np.sort(np.concatenate(arrs)) for k, arrs in shards.items()
        }


def save_table_npz(path: str, table: dict) -> None:
    """Dump a host-solve table ({pos: (value, remoteness)}) as one .npz.

    Always PLAIN npz (allow_block_framing=False): ``--table-out`` output
    is a user-facing artifact read by plain np.load downstream — the
    checkpoint compression knob must not reshape it.
    """
    states = np.array(sorted(table), dtype=np.uint64)
    values = jnp.asarray(
        np.array([table[int(s)][0] for s in states], dtype=np.uint8)
    )
    rems = jnp.asarray(
        np.array([table[int(s)][1] for s in states], dtype=np.int32)
    )
    _savez(
        path, allow_block_framing=False,
        states=states, cells=np.asarray(pack_cells(values, rems)),
    )


def save_result_npz(path: str, result) -> None:
    """Dump a SolveResult's full table as one .npz (packed cells per
    level). Plain npz always — see save_table_npz."""
    arrays = {}
    for level, table in result.levels.items():
        cells = np.asarray(
            pack_cells(jnp.asarray(table.values), jnp.asarray(table.remoteness))
        )
        arrays[f"states_{level:04d}"] = table.states
        arrays[f"cells_{level:04d}"] = cells
    _savez(path, allow_block_framing=False, **arrays)
