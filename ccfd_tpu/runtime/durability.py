"""Durable-state integrity plane: checksummed artifacts, quarantine,
last-good recovery.

PR 1 made the network edges fallible and PR 11 made the device fallible,
but every durable artifact the platform trusts at restart — champion
checkpoints, the ``versions.json`` lineage, recovery cuts, engine
snapshots, the usertask/drift npz files — was read back with zero
integrity verification: a bit-flipped ``params.npz`` or a torn lineage
file either crashed bring-up or silently served garbage params. The bus
log already shows the house style (CRC-framed records, torn tails
truncated to the valid prefix on reopen, ``bus/log.py``); this module
extends that guarantee to everything else on disk and is the ONE seam
every persistent writer/reader goes through.

Three layers:

- :func:`atomic_write_bytes` — the atomic-write idiom the codebase had
  hand-rolled in eight places, centralized and hardened: unique tmp +
  write + **fsync** + rename (the hand-rolled copies skipped the fsync,
  so a power loss could survive the rename but not the data — exactly
  the torn file the read side then has to catch). Every storage fault in
  the taxonomy (``runtime/faults.py`` storage class: ``torn_write``,
  ``rename_lost``, ``bitrot``, ``enospc``, ``fsync_fail``,
  ``slow_disk``) injects HERE, so the whole failure surface is drillable
  on CPU CI.
- :func:`write_artifact` / :func:`read_artifact` — the payload is framed
  under a one-line sha256 header (``CCFDSUM1 <hex> <len>\\n``), and the
  read side VERIFIES it: a corrupt file is **quarantined** (renamed to
  ``*.corrupt``, counted in ``ccfd_storage_corrupt_total{artifact}``,
  reported to the FlightRecorder) and the read **falls back to the
  last-good retained generation** instead of crashing bring-up or
  serving the corruption. A file without the frame reads as a legacy
  artifact (accepted, counted unverified) so pre-existing state keeps
  loading.
- generation retention — every :func:`write_artifact` also lands a copy
  at ``<path>.g<seq>`` and prunes past ``retain`` (default 3), the way
  ``CheckpointManager.keep`` already retains step dirs, so single-file
  artifacts (lineage, recovery cuts, engine snapshots) always have a
  last-good to fall back to.

Writes are **best-effort by default**: the in-memory state every caller
here holds is authoritative, and a full disk (or an injected
``enospc``) must degrade durability — counted in
``ccfd_storage_write_errors_total{artifact}`` — not crash the serving
plane. Interchange documents read by humans/Grafana (incident bundles,
profile artifacts) keep their plain-JSON bodies and get a ``.sha256``
sidecar instead of a frame (:func:`write_json_interchange`).

Metrics ride a process-wide tally (this module is called from
constructors that hold no registry); the operator binds the scraped
registry via :func:`bind_registry`, which replays the counts collected
before binding. :func:`sweep_tmp` removes the orphan ``*.tmp`` debris a
crash mid-write leaves behind (``ccfd_storage_tmp_swept_total``) and is
called from the stateful components' constructors at bring-up.

When NOTHING verifies — every generation of the champion checkpoint is
corrupt — serving unverified params is not an option for a fraud
system: :class:`StoragePinGate` pins the router's degradation ladder to
the rules tier through the PR 11 heal-gate seam (``device_allowed`` +
the new ``host_allowed``) until a verified tree is published again.
"""

from __future__ import annotations

import errno
import hashlib
import itertools
import json
import logging
import os
import threading
import time
from typing import Any, Callable, Mapping

log = logging.getLogger(__name__)

MAGIC = b"CCFDSUM1 "

# artifact-labelled metric short names; _PLAIN have no labels
_ARTIFACT_METRICS = ("corrupt", "fallback", "write_errors", "verified",
                     "unverified")
_PLAIN_METRICS = ("tmp_swept", "log_truncated_records")


class CorruptArtifactError(Exception):
    """No verifiable copy of a durable artifact exists (the main file and
    every retained generation failed verification)."""


_mu = threading.RLock()
_counts: dict[tuple[str, str], int] = {}  # (metric, artifact|"") -> n
_registry = None
_prom: dict[str, Any] = {}
_recorder: Callable[[Mapping[str, Any]], Any] | None = None
_tmp_seq = itertools.count()
_defaults = {"retain": 3, "fsync": True, "sweep": True}


def configure(retain: int | None = None, fsync: bool | None = None,
              sweep: bool | None = None) -> None:
    """Set the module defaults (the operator feeds the CR ``durability:``
    block here). Per-call arguments still win."""
    if retain is not None:
        _defaults["retain"] = max(0, int(retain))
    if fsync is not None:
        _defaults["fsync"] = bool(fsync)
    if sweep is not None:
        _defaults["sweep"] = bool(sweep)


def default_retain() -> int:
    return int(_defaults["retain"])


def bind_registry(registry) -> None:
    """Attach a scraped registry: creates the ``ccfd_storage_*`` counters
    and replays any tallies collected before binding (constructors run
    before the operator can wire metrics).

    The tallies are PROCESS-lifetime by design — a re-bind (a second
    Platform brought up in the same process) replays the full history
    into the fresh registry, so absolute counter values span the
    process, like the fault plans' ``injected`` tallies. ``rate()``
    consumers are unaffected; in-process consumers wanting a window
    snapshot :func:`counts` and diff."""
    global _registry
    with _mu:
        _registry = registry
        _prom.clear()
        _prom["corrupt"] = registry.counter(
            "ccfd_storage_corrupt_total",
            "corrupt durable artifacts detected (and quarantined)")
        _prom["fallback"] = registry.counter(
            "ccfd_storage_fallback_total",
            "reads served from a last-good retained generation")
        _prom["write_errors"] = registry.counter(
            "ccfd_storage_write_errors_total",
            "durable writes that failed (artifact kept last-good)")
        _prom["verified"] = registry.counter(
            "ccfd_storage_verified_reads_total",
            "artifact reads with a matching sha256 frame")
        _prom["unverified"] = registry.counter(
            "ccfd_storage_unverified_reads_total",
            "legacy (unframed) artifact reads accepted unverified")
        _prom["tmp_swept"] = registry.counter(
            "ccfd_storage_tmp_swept_total",
            "orphaned *.tmp files removed by the startup sweep")
        _prom["log_truncated_records"] = registry.counter(
            "ccfd_storage_log_truncated_records_total",
            "valid bus-log records dropped past a mid-file corrupt frame")
        for (short, artifact), n in _counts.items():
            c = _prom.get(short)
            if c is None or n <= 0:
                continue
            if short in _ARTIFACT_METRICS:
                c.inc(n, labels={"artifact": artifact})
            else:
                c.inc(n)


def set_recorder(fn: Callable[[Mapping[str, Any]], Any] | None) -> None:
    """FlightRecorder hook: called with a trigger mapping (``type``,
    ``artifact``, ``path``) on every quarantine, so corruption lands a
    post-mortem bundle like any other incident."""
    global _recorder
    _recorder = fn


def note(metric: str, n: int = 1, artifact: str = "") -> None:
    """Count one integrity event (public: ``bus/log.py`` counts mid-file
    log corruption here)."""
    if n <= 0:
        return
    with _mu:
        _counts[(metric, artifact)] = _counts.get((metric, artifact), 0) + n
        c = _prom.get(metric)
        if c is not None:
            if metric in _ARTIFACT_METRICS:
                c.inc(n, labels={"artifact": artifact})
            else:
                c.inc(n)


def counts() -> dict[str, dict[str, int]]:
    """{metric: {artifact: n}} snapshot of every tally so far."""
    with _mu:
        out: dict[str, dict[str, int]] = {}
        for (metric, artifact), n in _counts.items():
            out.setdefault(metric, {})[artifact] = n
        return out


def _notify_quarantine(artifact: str, path: str, dest: str) -> None:
    rec = _recorder
    if rec is None:
        return
    try:
        rec({"type": "storage_corrupt", "artifact": artifact,
             "path": path, "quarantined_to": dest})
    except Exception:  # noqa: BLE001 - post-mortem plumbing must not
        log.exception("storage quarantine recorder hook failed")


# ---------------------------------------------------------------------------
# the atomic-write seam (all storage faults inject here)
# ---------------------------------------------------------------------------


def _storage_plan():
    from ccfd_tpu.runtime import faults

    return faults.storage_faults()


def _flip_byte(path: str) -> None:
    """In-place single-byte corruption of a landed file (the ``bitrot``
    injection; also the drill helper tools/tests corrupt artifacts with)."""
    try:
        size = os.path.getsize(path)
        if size == 0:
            return
        off = size // 2
        with open(path, "r+b") as f:
            f.seek(off)
            b = f.read(1)
            f.seek(off)
            f.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")
    except OSError:
        log.exception("bitrot injection failed for %s", path)


def flip_bytes(path: str) -> None:
    """Deliberately corrupt an on-disk artifact (drills/tests)."""
    _flip_byte(path)


def atomic_write_bytes(path: str, data: bytes, fsync: bool | None = None,
                       artifact: str = "artifact") -> None:
    """Unique tmp + write + fsync + rename. Raises OSError on failure
    (injected or real); a failed write never touches the previous
    artifact, though it may leave an orphan ``*.tmp`` for the startup
    sweep — exactly what a crash mid-write leaves."""
    fsync = _defaults["fsync"] if fsync is None else bool(fsync)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    plan = _storage_plan()

    def draw(kind: str):
        return plan.draw(kind) if plan is not None else None

    s = draw("slow_disk")
    if s is not None:
        time.sleep(s.ms / 1e3)
    if draw("enospc") is not None:
        raise OSError(errno.ENOSPC, "injected ENOSPC", path)
    tmp = f"{path}.{os.getpid()}.{next(_tmp_seq)}.tmp"
    torn = draw("torn_write")
    fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        if torn is not None:
            # the crash-mid-write case: a prefix lands, the process dies
            # before the rename — the artifact keeps its previous bytes
            # and the orphan tmp waits for the sweep
            os.write(fd, data[: max(0, int(len(data) * torn.frac))])
            raise OSError(errno.EIO, "injected torn write", tmp)
        os.write(fd, data)
        if fsync:
            if draw("fsync_fail") is not None:
                raise OSError(errno.EIO, "injected fsync failure", tmp)
            os.fsync(fd)
    finally:
        os.close(fd)
    if draw("rename_lost") is not None:
        # the metadata-lost case: data was written and synced but the
        # rename never lands (journal lost on power cut) — the caller
        # believes the write succeeded, the artifact keeps its previous
        # bytes, the tmp is crash debris for the sweep
        return
    os.replace(tmp, path)
    if fsync:
        # the rename itself must survive a host crash: sync the directory
        try:
            dfd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:  # pragma: no cover - platform-dependent
            pass
    if draw("bitrot") is not None:
        # latent media corruption surfacing after a successful write —
        # the read side's quarantine + last-good fallback must catch it
        _flip_byte(path)


# ---------------------------------------------------------------------------
# framed artifacts + generation retention
# ---------------------------------------------------------------------------


def frame(payload: bytes) -> bytes:
    """``CCFDSUM1 <sha256hex> <len>\\n<payload>`` — self-verifying in one
    file, so there is no payload-vs-sidecar rename race to mis-read."""
    h = hashlib.sha256(payload).hexdigest()
    return MAGIC + h.encode() + (" %d\n" % len(payload)).encode() + payload


def parse_frame(data: bytes) -> tuple[bytes | None, bool]:
    """-> (payload, framed). ``(data, False)`` for a legacy (unframed)
    file; ``(None, True)`` for a framed file that fails verification
    (torn, truncated, bit-flipped)."""
    if not data.startswith(MAGIC):
        return data, False
    nl = data.find(b"\n", len(MAGIC))
    if nl < 0:
        return None, True
    try:
        hexdigest, length = data[len(MAGIC):nl].split()
        length = int(length)
    except ValueError:
        return None, True
    payload = data[nl + 1:]
    if (len(payload) != length
            or hashlib.sha256(payload).hexdigest() != hexdigest.decode(
                "ascii", "replace")):
        return None, True
    return payload, True


def scan_frames(data: bytes) -> tuple[list[tuple[int, bytes]], int, bool]:
    """Streaming scan of CONCATENATED :func:`frame` blocks (append-only
    logs, e.g. the decision-audit segments) -> ``([(start_offset,
    payload), ...], valid_prefix_bytes, torn)``. Verification stops at
    the first bad frame: in an append-only file everything after it
    postdates the corruption and is unreachable — the caller truncates
    to the valid prefix (the bus-log reopen contract). One scanner so
    the frame format has a single owner (:func:`parse_frame` handles
    the one-frame-per-file artifacts)."""
    frames: list[tuple[int, bytes]] = []
    pos = 0
    n = len(data)
    while pos < n:
        if not data.startswith(MAGIC, pos):
            return frames, pos, True
        nl = data.find(b"\n", pos + len(MAGIC))
        if nl < 0:
            return frames, pos, True
        try:
            hexdigest, length = data[pos + len(MAGIC):nl].split()
            length = int(length)
        except ValueError:
            return frames, pos, True
        end = nl + 1 + length
        if end > n:
            return frames, pos, True
        payload = data[nl + 1:end]
        if hashlib.sha256(payload).hexdigest() != hexdigest.decode(
                "ascii", "replace"):
            return frames, pos, True
        frames.append((pos, payload))
        pos = end
    return frames, pos, False


def _generations(path: str) -> list[tuple[int, str]]:
    """Retained generations of ``path``, ascending ``[(seq, path)]``."""
    d = os.path.dirname(os.path.abspath(path))
    base = os.path.basename(path) + ".g"
    out: list[tuple[int, str]] = []
    try:
        names = os.listdir(d)
    except OSError:
        return out
    for name in names:
        if name.startswith(base):
            tail = name[len(base):]
            if tail.isdigit():
                out.append((int(tail), os.path.join(d, name)))
    return sorted(out)


def has_generations(path: str) -> bool:
    return bool(_generations(path))


def write_artifact(path: str, payload: bytes, artifact: str = "artifact",
                   retain: int | None = None, fsync: bool | None = None,
                   best_effort: bool = True) -> bool:
    """Framed, checksummed, atomic write + generation retention. Returns
    False (and counts ``write_errors``) when the write failed and
    ``best_effort`` — the previous artifact (or its generations) stays
    the last-good state a reader falls back to."""
    data = frame(payload)
    try:
        atomic_write_bytes(path, data, fsync=fsync, artifact=artifact)
    except OSError as e:
        note("write_errors", artifact=artifact)
        log.error("durable write of %s (%s) failed: %s — keeping last-good",
                  path, artifact, e)
        if not best_effort:
            raise
        return False
    r = _defaults["retain"] if retain is None else max(0, int(retain))
    if r > 0:
        try:
            # a full SECOND copy, deliberately not an os.link of the main
            # file: a hard link shares the inode, so later bitrot of the
            # shared extent would corrupt main AND its newest generation
            # together — the exact failure the generation exists to
            # survive. Artifacts at this seam are small, low-rate JSON/
            # npz; the doubled write is the price of a physically
            # independent last-good copy.
            gens = _generations(path)
            seq = (gens[-1][0] + 1) if gens else 1
            atomic_write_bytes(f"{path}.g{seq:08d}", data, fsync=fsync,
                               artifact=artifact)
            for _s, p in _generations(path)[:-r]:
                try:
                    os.unlink(p)
                except OSError:
                    pass
        except OSError as e:
            note("write_errors", artifact=artifact)
            log.warning("generation retention for %s failed: %s", path, e)
    return True


def _quarantine(path: str, artifact: str) -> None:
    dest = path + ".corrupt"
    try:
        os.replace(path, dest)
    except OSError:
        dest = "<unmovable>"
    note("corrupt", artifact=artifact)
    log.error("corrupt %s artifact %s quarantined to %s", artifact, path,
              dest)
    _notify_quarantine(artifact, path, dest)


def read_artifact(path: str, artifact: str = "artifact",
                  fallback: bool = True, quarantine: bool = True) -> bytes:
    """Verified read. A framed file that fails its sha256 is quarantined
    (``*.corrupt``) and the newest verifiable retained generation is
    served instead (``ccfd_storage_fallback_total``). Raises
    FileNotFoundError when nothing was ever written, and
    :class:`CorruptArtifactError` when data existed but no copy
    verifies. ``quarantine=False`` peeks without touching disk state
    (best-effort probes); ``fallback=False`` raises on the main file's
    verdict alone (artifacts with their own retention, e.g. checkpoint
    step dirs)."""
    data: bytes | None = None
    read_failed = False
    try:
        with open(path, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        pass
    except OSError as e:
        # an UNREADABLE main file (EIO from dying media, EACCES) is the
        # hardware-failure case this plane exists for: treat it exactly
        # like a failed checksum — count, quarantine best-effort, and
        # fall back to the retained generations instead of propagating
        read_failed = True
        log.error("%s artifact %s unreadable (%s)", artifact, path, e)
    if data is not None:
        payload, framed = parse_frame(data)
        if payload is not None:
            note("verified" if framed else "unverified", artifact=artifact)
            return payload
    if data is not None or read_failed:
        if quarantine:
            _quarantine(path, artifact)
        else:
            note("corrupt", artifact=artifact)
    if not fallback:
        if data is None and not read_failed:
            raise FileNotFoundError(path)
        raise CorruptArtifactError(
            f"{artifact} artifact {path} failed verification")
    gens = _generations(path)
    for seq, gp in reversed(gens):
        try:
            with open(gp, "rb") as f:
                gdata = f.read()
        except OSError:
            continue
        payload, framed = parse_frame(gdata)
        if payload is not None and framed:
            note("fallback", artifact=artifact)
            log.warning("%s artifact %s served from last-good generation "
                        "g%d", artifact, path, seq)
            return payload
        # a corrupt generation must not be re-tried on every read
        note("corrupt", artifact=artifact)
        if quarantine:
            try:
                os.replace(gp, gp + ".corrupt")
            except OSError:
                pass
    if data is None and not read_failed and not gens:
        raise FileNotFoundError(path)
    raise CorruptArtifactError(
        f"no verifiable copy of {artifact} artifact {path}")


def verify_file(path: str) -> bool | None:
    """Peek verification: None when missing, True for a verified frame OR
    a legacy unframed file (nothing to check against), False when a
    frame fails its checksum. Never mutates disk state."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        return None
    except OSError:
        return False
    payload, _framed = parse_frame(data)
    return payload is not None


def write_json_artifact(path: str, doc: Any, artifact: str = "artifact",
                        retain: int | None = None, fsync: bool | None = None,
                        best_effort: bool = True, **dump_kw: Any) -> bool:
    return write_artifact(
        path, json.dumps(doc, **dump_kw).encode(), artifact=artifact,
        retain=retain, fsync=fsync, best_effort=best_effort)


def read_json_artifact(path: str, artifact: str = "artifact",
                       fallback: bool = True, quarantine: bool = True) -> Any:
    return json.loads(read_artifact(path, artifact=artifact,
                                    fallback=fallback,
                                    quarantine=quarantine))


# ---------------------------------------------------------------------------
# interchange documents (plain body + .sha256 sidecar)
# ---------------------------------------------------------------------------


def write_json_interchange(path: str, doc: Any, artifact: str = "interchange",
                           best_effort: bool = True, **dump_kw: Any) -> bool:
    """Crash-safe write for documents external readers ``json.load``
    directly (incident bundles, profile artifacts): the body
    stays plain JSON; integrity rides a ``<path>.sha256`` sidecar written
    AFTER the body, so every crash window leaves either the old pair or
    a new body whose missing/stale sidecar reads as unverified — never a
    false quarantine of good data."""
    dump_kw.setdefault("indent", 1)
    body = (json.dumps(doc, **dump_kw) + "\n").encode()
    try:
        # remove the stale sidecar first: a crash after the body rename
        # must not leave the OLD hash beside the NEW body
        try:
            os.unlink(path + ".sha256")
        except FileNotFoundError:
            pass
        atomic_write_bytes(path, body, artifact=artifact)
        atomic_write_bytes(path + ".sha256",
                           hashlib.sha256(body).hexdigest().encode() + b"\n",
                           artifact=artifact)
    except OSError as e:
        note("write_errors", artifact=artifact)
        log.error("interchange write of %s failed: %s", path, e)
        if not best_effort:
            raise
        return False
    return True


def verify_interchange(path: str) -> bool | None:
    """True/False per the sidecar; None when the file or its sidecar is
    missing (legacy / mid-crash window: accept unverified)."""
    try:
        with open(path, "rb") as f:
            body = f.read()
        with open(path + ".sha256", "rb") as f:
            want = f.read().strip().decode("ascii", "replace")
    except FileNotFoundError:
        return None
    except OSError:
        return False
    return hashlib.sha256(body).hexdigest() == want


# ---------------------------------------------------------------------------
# directory manifests (orbax checkpoint dirs: many files, none ours to frame)
# ---------------------------------------------------------------------------

MANIFEST_NAME = "ccfd_manifest.json"


def _dir_files(dirpath: str) -> list[str]:
    out = []
    for root, _dirs, files in os.walk(dirpath):
        for name in files:
            p = os.path.join(root, name)
            rel = os.path.relpath(p, dirpath)
            if rel == MANIFEST_NAME or rel.endswith(".tmp"):
                continue
            out.append(rel)
    return sorted(out)


def write_dir_manifest(dirpath: str, artifact: str = "checkpoint") -> bool:
    """Checksum manifest over every file in a directory artifact (the
    orbax checkpoint path — its internal files are not ours to frame)."""
    manifest: dict[str, Any] = {}
    try:
        for rel in _dir_files(dirpath):
            with open(os.path.join(dirpath, rel), "rb") as f:
                manifest[rel] = hashlib.sha256(f.read()).hexdigest()
    except OSError as e:
        note("write_errors", artifact=artifact)
        log.error("manifest build for %s failed: %s", dirpath, e)
        return False
    return write_json_artifact(os.path.join(dirpath, MANIFEST_NAME),
                               manifest, artifact=artifact, retain=0)


def verify_dir_manifest(dirpath: str, artifact: str = "checkpoint"
                        ) -> bool | None:
    """True/False per the manifest; None when no manifest exists (a
    legacy checkpoint dir: accepted unverified)."""
    mpath = os.path.join(dirpath, MANIFEST_NAME)
    try:
        manifest = read_json_artifact(mpath, artifact=artifact,
                                      fallback=False, quarantine=False)
    except FileNotFoundError:
        return None
    except (CorruptArtifactError, ValueError):
        return False
    try:
        for rel, want in manifest.items():
            with open(os.path.join(dirpath, rel), "rb") as f:
                if hashlib.sha256(f.read()).hexdigest() != want:
                    return False
    except OSError:
        return False
    return True


# ---------------------------------------------------------------------------
# orphan-tmp sweep
# ---------------------------------------------------------------------------


def sweep_tmp(*dirs: str, enabled: bool | None = None) -> int:
    """Remove orphaned ``*.tmp`` files a crash mid-write left behind
    (e.g. the offsets.log compaction tmp in bus/log.py). Startup-only by
    contract: live writers use unique tmp names and rename within the
    same call, so any ``*.tmp`` present when a component CONSTRUCTS is
    debris. Counted in ``ccfd_storage_tmp_swept_total``."""
    if not (_defaults["sweep"] if enabled is None else enabled):
        return 0
    n = 0
    for d in dirs:
        if not d:
            continue
        try:
            names = os.listdir(d)
        except OSError:
            continue
        for name in names:
            if not name.endswith(".tmp"):
                continue
            try:
                os.unlink(os.path.join(d, name))
                n += 1
            except OSError:
                pass
    if n:
        note("tmp_swept", n)
        log.warning("startup sweep removed %d orphaned tmp file(s) from %s",
                    n, ", ".join(d for d in dirs if d))
    return n


# ---------------------------------------------------------------------------
# the rules-tier pin for unverifiable serving state
# ---------------------------------------------------------------------------


class StoragePinGate:
    """Heal-gate-shaped pin (``device_allowed`` + ``host_allowed``): when
    NO champion checkpoint generation verifies, the router must pin to
    the rules tier — the host tier would forward the very same
    unverified tree. Armed by the lifecycle controller's restore path,
    cleared when a verified tree is published again."""

    def __init__(self, registry=None):
        self._mu = threading.Lock()
        self._pinned = False
        self.reason: str | None = None
        self.pins = 0
        self._g = None
        if registry is not None:
            self._g = registry.gauge(
                "ccfd_storage_pinned",
                "1 while serving is pinned to the rules tier because no "
                "durable params generation verifies",
            )
            self._g.set(0)

    @property
    def pinned(self) -> bool:
        with self._mu:
            return self._pinned

    def pin(self, reason: str) -> None:
        with self._mu:
            if not self._pinned:
                self.pins += 1
            self._pinned = True
            self.reason = reason
            if self._g is not None:
                self._g.set(1)
        log.error("storage pin: serving pinned to the rules tier (%s)",
                  reason)

    def unpin(self) -> None:
        with self._mu:
            was = self._pinned
            self._pinned = False
            self.reason = None
            if self._g is not None:
                self._g.set(0)
        if was:
            log.warning("storage pin cleared: verified params published")

    # the router's heal-gate surface
    def device_allowed(self) -> bool:
        return not self.pinned

    def host_allowed(self) -> bool:
        return not self.pinned


class ComposedHealGate:
    """AND-composition of heal-gate-shaped objects: the operator hands
    the router ONE gate built from the storage pin and (when the heal
    component is up) the DeviceSupervisor. ``host_allowed`` consults
    only gates that define it (the DeviceSupervisor pins the device but
    the host tier stays the heal ladder's fallback)."""

    def __init__(self, *gates: Any):
        self.gates = tuple(g for g in gates if g is not None)

    def device_allowed(self) -> bool:
        return all(g.device_allowed() for g in self.gates)

    def host_allowed(self) -> bool:
        return all(
            g.host_allowed() for g in self.gates
            if callable(getattr(g, "host_allowed", None))
        )
