"""Persistent tuning registry — offline results that survive the process.

The thesis' methodology is *explore cheap offline, validate accurately,
adapt at run time*; this module is the missing persistence layer between
those phases.  Following MITuna's design (tuned kernel configs keyed by
problem + architecture in a database), every tuning result is stored under
a four-part key::

    (kind, problem signature, machine fingerprint, cost-model version)

* ``kind``        — what was tuned ("conv_schedule", "matmul_schedule",
                    "conv_sweep", or a runtime-measurement kind).
* ``problem``     — the layer / matmul shape, canonicalised to a dict.
* ``machine``     — fingerprint of the :class:`MachineModel` /
                    :class:`TPUSpec` (or the live JAX runtime) the result
                    is valid for; a different machine invalidates it.
* ``cost_model``  — :data:`repro.core.cost_model.COST_MODEL_VERSION`;
                    bumping the model orphans stale predictions.

Storage is JSON-lines: one canonical (sorted-keys, compact) JSON object
per line.  Writers append a single line under ``O_APPEND`` — concurrent
writers from several processes interleave whole lines, never bytes — and
readers replay the log last-write-wins.  :meth:`TuningRegistry.compact`
rewrites the file sorted by key, which makes registry *contents a pure
function of the record set*: a parallel sweep compacts to byte-identical
bytes as the serial sweep.
"""
from __future__ import annotations

import dataclasses
import fcntl
import functools
import hashlib
import json
import logging
import os
import tempfile
import threading
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

log = logging.getLogger("repro.registry")

SCHEMA_VERSION = 1

_ENV_PATH = "REPRO_TUNE_REGISTRY"
_DEFAULT_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "tuning.jsonl")


# ---------------------------------------------------------------------------
# Canonical JSON + fingerprints
# ---------------------------------------------------------------------------

def canonical_json(obj: Any) -> str:
    """Deterministic serialisation: sorted keys, no whitespace."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


@functools.lru_cache(maxsize=1024)
def _fingerprint_dataclass(obj: Any) -> str:
    """Memoized digest of a frozen dataclass.

    Machine descriptors (MachineModel, TPUSpec) are frozen dataclasses,
    so their digest is memoizable per-process: every cached_* call needs
    the machine fingerprint, and without this cache it re-serialised and
    re-hashed the same object on every lookup."""
    payload = {"__class__": type(obj).__name__,
               **dataclasses.asdict(obj)}
    digest = hashlib.sha256(canonical_json(payload).encode("utf-8"))
    return digest.hexdigest()[:12]


def fingerprint(obj: Any) -> str:
    """Stable 12-hex digest of a dataclass / dict / tuple describing the
    machine (``MachineModel``, ``TPUSpec``, ...).  Hashable (frozen)
    dataclasses are memoized per-process."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        try:
            return _fingerprint_dataclass(obj)
        except TypeError:
            # unhashable (mutable) dataclass: compute without the cache
            payload = {"__class__": type(obj).__name__,
                       **dataclasses.asdict(obj)}
    else:
        payload = obj
    digest = hashlib.sha256(canonical_json(payload).encode("utf-8"))
    return digest.hexdigest()[:12]


def runtime_fingerprint() -> str:
    """Fingerprint of the live JAX runtime (for measured results).  A
    runtime that cannot be queried raises: a measurement filed under a
    made-up platform would be matched against the wrong machine."""
    import jax
    return fingerprint({"platform": jax.default_backend(),
                        "device_count": jax.device_count()})


# ---------------------------------------------------------------------------
# Keys and records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RegistryKey:
    """The four-part key every record is stored under (see module doc)."""

    kind: str
    problem: Tuple[Tuple[str, Any], ...]   # hashable canonical form
    machine: str                           # fingerprint
    cost_model: str                        # cost-model version string

    @staticmethod
    def make(kind: str, problem: Dict[str, Any], machine: str,
             cost_model: str) -> "RegistryKey":
        """Build a key from a problem dict (canonicalised to a tuple)."""
        return RegistryKey(kind, tuple(sorted(problem.items())), machine,
                           cost_model)

    def problem_dict(self) -> Dict[str, Any]:
        """The problem signature back as a plain dict."""
        return dict(self.problem)

    def to_dict(self) -> Dict[str, Any]:
        """JSON-serialisable form (inverse of :meth:`from_dict`)."""
        return {"kind": self.kind, "problem": self.problem_dict(),
                "machine": self.machine, "cost_model": self.cost_model}

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "RegistryKey":
        """Rebuild a key from its :meth:`to_dict` form."""
        return RegistryKey.make(d["kind"], d["problem"], d["machine"],
                                d["cost_model"])

    def canonical(self) -> str:
        """Canonical-JSON identity string (the in-memory map key)."""
        return canonical_json(self.to_dict())


@dataclasses.dataclass
class TuningRecord:
    """One tuning result: the winning configuration(s) plus costs.

    ``value`` is kind-specific (serialised schedules + predicted costs, or
    raw sweep arrays); ``measured`` is filled in by the adaptive runtime's
    write-back and refines the offline prediction.  Records deliberately
    carry no wall-clock timestamps so that registry bytes are a pure
    function of the tuning inputs (serial == parallel, re-run == re-run).
    """
    key: RegistryKey
    value: Dict[str, Any]
    measured: Optional[Dict[str, Any]] = None
    source: str = "offline"

    def to_dict(self) -> Dict[str, Any]:
        """JSON-serialisable form, schema-stamped (one JSONL line)."""
        return {"schema": SCHEMA_VERSION, "key": self.key.to_dict(),
                "value": self.value, "measured": self.measured,
                "source": self.source}

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "TuningRecord":
        """Rebuild a record from its :meth:`to_dict` form."""
        return TuningRecord(key=RegistryKey.from_dict(d["key"]),
                            value=d["value"],
                            measured=d.get("measured"),
                            source=d.get("source", "offline"))


# ---------------------------------------------------------------------------
# Schedule (de)serialisation helpers
# ---------------------------------------------------------------------------

def schedule_to_dict(sched: Any) -> Dict[str, Any]:
    """Serialise any schedule dataclass to a typed JSON dict."""
    from repro.core import schedule as sch
    if isinstance(sched, sch.ConvSchedule):
        return {"type": "conv", "grid_order": list(sched.grid_order),
                "block": sched.block_dict()}
    if isinstance(sched, sch.MatmulSchedule):
        return {"type": "matmul", "grid_order": list(sched.grid_order),
                "block": sched.block_dict(),
                "resident_rhs": bool(sched.resident_rhs)}
    if isinstance(sched, sch.FlashAttentionSchedule):
        return {"type": "flash_attention",
                "block_q": int(sched.block_q),
                "block_kv": int(sched.block_kv)}
    if isinstance(sched, sch.DecodeAttentionSchedule):
        return {"type": "decode_attention",
                "block_kv": int(sched.block_kv)}
    if isinstance(sched, sch.SSMScanSchedule):
        return {"type": "ssm_scan", "block_d": int(sched.block_d)}
    if isinstance(sched, sch.SparseConvSchedule):
        return {"type": "sparse_conv", "block": sched.block_dict()}
    return {"type": "opaque", "repr": repr(sched)}


def schedule_from_dict(d: Dict[str, Any]) -> Any:
    """Inverse of :func:`schedule_to_dict` (raises on unknown types)."""
    from repro.core import schedule as sch
    if d["type"] == "conv":
        return sch.ConvSchedule.make(d["grid_order"], d["block"])
    if d["type"] == "matmul":
        return sch.MatmulSchedule.make(d["grid_order"], d["block"],
                                       d.get("resident_rhs", False))
    if d["type"] == "flash_attention":
        return sch.FlashAttentionSchedule(int(d["block_q"]),
                                          int(d["block_kv"]))
    if d["type"] == "decode_attention":
        return sch.DecodeAttentionSchedule(int(d["block_kv"]))
    if d["type"] == "ssm_scan":
        return sch.SSMScanSchedule(int(d["block_d"]))
    if d["type"] == "sparse_conv":
        return sch.SparseConvSchedule.make(d["block"])
    raise ValueError(f"cannot rebuild schedule of type {d['type']!r}")


def cost_to_dict(cost: Any) -> Dict[str, Any]:
    """Serialise a predicted-cost dataclass to a plain dict."""
    return dataclasses.asdict(cost)


def cost_from_dict(d: Dict[str, Any]) -> Any:
    """Inverse of :func:`cost_to_dict` (KernelCost fields)."""
    from repro.core.cost_model import KernelCost
    return KernelCost(**d)


# ---------------------------------------------------------------------------
# Cost-model tier provenance
# ---------------------------------------------------------------------------

# Which cost-model tier produced each record kind (docs/TUNING.md): the
# roofline-style analytic models, the ECM layer-condition tier, or the
# exact trace-driven simulator.  A record may override this statically
# derived tier with an explicit ``value["tier"]`` (e.g. an ``ecm_sweep``
# winner that an exact consultation decided).
KIND_TIERS: Dict[str, str] = {
    "conv_sweep": "roofline",
    "conv_schedule": "roofline",
    "matmul_schedule": "roofline",
    "flash_attention_schedule": "roofline",
    "decode_attention_schedule": "roofline",
    "ssm_scan_schedule": "roofline",
    "sparse_conv_schedule": "roofline",
    "ecm_sweep": "ecm",
    "ecm_correction": "ecm",
    "exact_sweep": "exact",
}


def kind_tier(kind: str) -> str:
    """Default cost-model tier for a record kind ("other" if unknown)."""
    return KIND_TIERS.get(kind, "other")


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

class TuningRegistry:
    """Versioned on-disk store of tuning results (JSON-lines).

    ``path=None`` keeps the registry purely in memory (useful for tests
    and one-shot scripts).  All mutation goes through :meth:`put` /
    :meth:`record_measurement` / :meth:`invalidate`; with a path set, each
    ``put`` appends one line (crash-safe, concurrent-writer-safe) and
    :meth:`compact` canonicalises the file.
    """

    def __init__(self, path: Optional[str] = None, autoload: bool = True):
        """Open (and by default replay) the registry at ``path``."""
        self.path = path
        self._records: Dict[str, TuningRecord] = {}
        self._lock = threading.Lock()
        self.malformed_lines = 0
        if path and autoload:
            self.load()

    # -- construction ---------------------------------------------------
    @staticmethod
    def default_path() -> str:
        """Registry path: env ``REPRO_TUNE_REGISTRY`` or the user cache."""
        return os.environ.get(_ENV_PATH, _DEFAULT_PATH)

    @classmethod
    def default(cls) -> "TuningRegistry":
        """Process-wide default registry (env ``REPRO_TUNE_REGISTRY`` or
        ``~/.cache/repro/tuning.jsonl``)."""
        global _DEFAULT_REGISTRY
        path = cls.default_path()
        if _DEFAULT_REGISTRY is None or _DEFAULT_REGISTRY.path != path:
            _DEFAULT_REGISTRY = cls(path)
        return _DEFAULT_REGISTRY

    # -- persistence ----------------------------------------------------
    def load(self) -> int:
        """Replay the JSONL log (last write per key wins).  Unknown or
        future-schema lines are skipped, not fatal; torn/malformed lines
        (e.g. a crash mid-append) are counted in ``malformed_lines`` and
        reported once via a warning, never raised."""
        if not self.path or not os.path.exists(self.path):
            return 0
        n = 0
        bad = 0
        with self._lock:
            with open(self.path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        d = json.loads(line)
                        if d.get("schema", 0) > SCHEMA_VERSION:
                            # Future-schema lines are intentional skips
                            # (forward compat), not corruption.
                            continue
                        rec = TuningRecord.from_dict(d)
                    except (ValueError, KeyError, TypeError):
                        bad += 1
                        continue
                    self._records[rec.key.canonical()] = rec
                    n += 1
            self.malformed_lines += bad
        if bad:
            log.warning("registry %s: skipped %d malformed line(s) "
                        "(torn append or corruption); kept %d records",
                        self.path, bad, n)
        return n

    def _append_line(self, rec: TuningRecord) -> None:
        """Durably append one canonical JSONL line for ``rec``."""
        if not self.path:
            return
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        line = canonical_json(rec.to_dict()) + "\n"
        # One O_APPEND write per record: whole lines interleave across
        # concurrent writers, bytes never do.  If a previous writer died
        # mid-append the file can end without a newline; lead with one so
        # this record starts a fresh line instead of extending the torn
        # tail (load() then skips exactly one malformed line).  The tail
        # check and the append hold one exclusive lock, so the check never
        # sees another writer's line half-written (which would add a
        # blank line).
        buf = line.encode("utf-8")
        fd = os.open(self.path, os.O_RDWR | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            size = os.fstat(fd).st_size
            if size and os.pread(fd, 1, size - 1) != b"\n":
                buf = b"\n" + buf
            os.write(fd, buf)
            os.fsync(fd)  # durable before we report the record stored
        finally:
            os.close(fd)  # releases the lock

    def compact(self) -> None:
        """Rewrite the log as one canonical line per key, sorted by key.

        After compaction the file bytes depend only on the record set —
        the property the parallel-sweep determinism guarantee rests on.
        Atomic (write temp + rename).
        """
        if not self.path:
            return
        with self._lock:
            items = sorted(self._records.items())
            dirname = os.path.dirname(os.path.abspath(self.path)) or "."
            os.makedirs(dirname, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp")
            try:
                with os.fdopen(fd, "w", encoding="utf-8") as f:
                    for _, rec in items:
                        f.write(canonical_json(rec.to_dict()) + "\n")
                os.replace(tmp, self.path)
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise

    def export_json(self, path: str) -> int:
        """Dump the current state as a single pretty JSON array."""
        recs = [rec.to_dict() for _, rec in sorted(self._records.items())]
        with open(path, "w", encoding="utf-8") as f:
            json.dump(recs, f, indent=2, sort_keys=True)
            f.write("\n")
        return len(recs)

    # -- access ---------------------------------------------------------
    def get(self, key: RegistryKey) -> Optional[TuningRecord]:
        """The record stored under ``key``, or None."""
        return self._records.get(key.canonical())

    def put(self, record: TuningRecord, persist: bool = True) -> None:
        """Store (and by default append-persist) one record."""
        with self._lock:
            self._records[record.key.canonical()] = record
        if persist:
            self._append_line(record)

    def record_measurement(self, key: RegistryKey,
                           best: Dict[str, Any],
                           time_s: float,
                           persist: bool = True) -> TuningRecord:
        """Adaptive write-back: attach a run-time measurement to ``key``.

        Creates the record if offline tuning never saw this problem (a
        purely run-time discovery is still worth persisting)."""
        rec = self.get(key)
        if rec is None:
            rec = TuningRecord(key=key, value={"schedules": [best]},
                               source="adaptive")
        rec.measured = {"best": best, "time_s": float(time_s)}
        self.put(rec, persist=persist)
        return rec

    def invalidate(self, kind: Optional[str] = None,
                   machine: Optional[str] = None,
                   cost_model: Optional[str] = None,
                   persist: bool = True) -> int:
        """Drop records matching all given filters (None = wildcard).
        ``invalidate()`` with no filters clears everything."""
        with self._lock:
            doomed = [ck for ck, rec in self._records.items()
                      if (kind is None or rec.key.kind == kind)
                      and (machine is None or rec.key.machine == machine)
                      and (cost_model is None
                           or rec.key.cost_model == cost_model)]
            for ck in doomed:
                del self._records[ck]
        if doomed and persist:
            self.compact()
        return len(doomed)

    def merge(self, other: "TuningRegistry",
              persist: bool = True) -> Dict[str, int]:
        """Content-addressed union with ``other`` (fleet sync).

        Records are addressed by their canonical key; identical records
        (byte-identical canonical JSON) dedupe for free.  A key conflict
        is resolved by :func:`prefer_record` — a deterministic total
        order (measured beats unmeasured, more ranked schedules beat
        fewer, then canonical bytes), so ``a.merge(b)`` and ``b.merge(a)``
        converge on the same record set regardless of merge direction.
        """
        stats = {"added": 0, "replaced": 0, "kept": 0, "identical": 0}
        for rec in other.records():
            mine = self.get(rec.key)
            if mine is None:
                self.put(rec, persist=persist)
                stats["added"] += 1
            elif canonical_json(mine.to_dict()) == \
                    canonical_json(rec.to_dict()):
                stats["identical"] += 1
            elif prefer_record(mine, rec) is mine:
                stats["kept"] += 1
            else:
                self.put(rec, persist=persist)
                stats["replaced"] += 1
        return stats

    def machines(self) -> List[str]:
        """Distinct machine fingerprints present in the record set."""
        return sorted({rec.key.machine for rec in self._records.values()})

    def keys(self) -> List[RegistryKey]:
        """All keys, sorted canonically."""
        return [rec.key for _, rec in sorted(self._records.items())]

    def records(self) -> Iterator[TuningRecord]:
        """All records in canonical key order."""
        for _, rec in sorted(self._records.items()):
            yield rec

    def __len__(self) -> int:
        """Number of distinct keys held."""
        return len(self._records)

    def __contains__(self, key: RegistryKey) -> bool:
        """Whether ``key`` has a stored record."""
        return key.canonical() in self._records

    def stats(self) -> Dict[str, Any]:
        """Summary counts: total, per kind, per cost-model tier (an
        explicit ``value["tier"]`` wins over the kind's default), and
        how many records carry run-time measurements."""
        by_kind: Dict[str, int] = {}
        by_tier: Dict[str, int] = {}
        measured = 0
        for rec in self._records.values():
            by_kind[rec.key.kind] = by_kind.get(rec.key.kind, 0) + 1
            tier = rec.value.get("tier") or kind_tier(rec.key.kind)
            by_tier[tier] = by_tier.get(tier, 0) + 1
            measured += rec.measured is not None
        return {"records": len(self._records), "by_kind": by_kind,
                "by_tier": by_tier, "measured": measured,
                "path": self.path,
                "malformed_lines": self.malformed_lines}


_DEFAULT_REGISTRY: Optional[TuningRegistry] = None


def prefer_record(a: TuningRecord, b: TuningRecord) -> TuningRecord:
    """Deterministic, order-independent conflict rule for merges: a
    measured record beats an unmeasured one, more ranked schedules beat
    fewer, and canonical bytes break the remaining ties (so the winner
    does not depend on which registry was merged into which)."""
    def rank(rec: TuningRecord):
        """Sort key of the conflict rule (higher wins)."""
        return (rec.measured is not None,
                len(rec.value.get("schedules", ())),
                len(rec.value.get("costs", ())))
    ra, rb = rank(a), rank(b)
    if ra != rb:
        return a if ra > rb else b
    ca, cb = canonical_json(a.to_dict()), canonical_json(b.to_dict())
    return a if ca <= cb else b


# ---------------------------------------------------------------------------
# Machine last-seen sidecar (fleet-scale eviction policy)
# ---------------------------------------------------------------------------
#
# Registry records deliberately carry no wall-clock timestamps (bytes are
# a pure function of the record set), so staleness lives in a sidecar:
# ``<registry>.machines.json`` maps machine fingerprint -> last-seen ISO
# date, stamped whenever a registry containing that fingerprint is merged.
# ``python -m repro.tune merge --evict-days N`` drops records whose
# fingerprint has not been seen for N days.

def machine_seen_path(registry_path: str) -> str:
    """Path of the last-seen sidecar next to a registry file."""
    return registry_path + ".machines.json"


def load_machine_seen(registry_path: str) -> Dict[str, str]:
    """Read the sidecar: machine fingerprint -> last-seen ISO date."""
    path = machine_seen_path(registry_path)
    if not os.path.exists(path):
        return {}
    try:
        with open(path, "r", encoding="utf-8") as f:
            d = json.load(f)
        return {str(k): str(v) for k, v in d.items()}
    except (ValueError, OSError):
        return {}


def save_machine_seen(registry_path: str, seen: Dict[str, str]) -> None:
    """Write the sidecar (sorted, pretty) next to the registry."""
    path = machine_seen_path(registry_path)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(dict(sorted(seen.items())), f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# Key builders for the repo's problem kinds
# ---------------------------------------------------------------------------

def conv_problem(layer: Any, elem_bytes: int = 2) -> Dict[str, Any]:
    """Canonical problem dict of a ConvLayer shape."""
    return {"oc": layer.oc, "ic": layer.ic, "h": layer.h, "w": layer.w,
            "kh": layer.kh, "kw": layer.kw, "elem_bytes": elem_bytes}


def conv_layer_from_problem(problem: Dict[str, Any]) -> Any:
    """Rebuild the ConvLayer a :func:`conv_problem` dict describes."""
    from repro.core.loopnest import ConvLayer
    return ConvLayer(problem["oc"], problem["ic"], problem["h"],
                     problem["w"], problem["kh"], problem["kw"])


def conv_schedule_key(layer: Any, spec: Any, elem_bytes: int = 2,
                      ) -> RegistryKey:
    """Key for a TPU conv-schedule ranking."""
    from repro.core.cost_model import COST_MODEL_VERSION
    return RegistryKey.make("conv_schedule", conv_problem(layer, elem_bytes),
                            fingerprint(spec), COST_MODEL_VERSION)


def matmul_schedule_key(m: int, n: int, k: int, spec: Any,
                        elem_bytes: int = 2) -> RegistryKey:
    """Key for a TPU matmul-schedule ranking."""
    from repro.core.cost_model import COST_MODEL_VERSION
    problem = {"m": m, "n": n, "k": k, "elem_bytes": elem_bytes}
    return RegistryKey.make("matmul_schedule", problem, fingerprint(spec),
                            COST_MODEL_VERSION)


def conv_sweep_key(layer: Any, machine: Any, threads: int = 1,
                   ) -> RegistryKey:
    """Key for a tier-1 720-permutation sweep signature."""
    from repro.core.cost_model import COST_MODEL_VERSION
    problem = conv_problem(layer, layer.elem_bytes)
    problem["threads"] = threads
    return RegistryKey.make("conv_sweep", problem, fingerprint(machine),
                            COST_MODEL_VERSION)


def ecm_sweep_key(layer: Any, machine: Any, threads: int = 1,
                  ) -> RegistryKey:
    """Key for a tier-2 ECM sweep winner (docs/TUNING.md).

    Versioned under :data:`repro.core.ecm.ECM_MODEL_VERSION`, not the
    tier-1 ``COST_MODEL_VERSION``: the two models evolve (and must
    invalidate their cached predictions) independently."""
    from repro.core.ecm import ECM_MODEL_VERSION
    problem = conv_problem(layer, layer.elem_bytes)
    problem["threads"] = threads
    return RegistryKey.make("ecm_sweep", problem, fingerprint(machine),
                            ECM_MODEL_VERSION)


def ecm_correction_key(machine: Any) -> RegistryKey:
    """Key for the machine's learned ECM correction coefficients.

    The "problem" is the correction's functional form (feature count +
    family), so a refit for the same machine overwrites in place while a
    feature change lands under a fresh key."""
    from repro.core.ecm import ECM_MODEL_VERSION, N_FEATURES
    problem = {"features": N_FEATURES, "form": "log-linear"}
    return RegistryKey.make("ecm_correction", problem,
                            fingerprint(machine), ECM_MODEL_VERSION)


def flash_attention_schedule_key(b: int, hq: int, hkv: int, s: int,
                                 d: int, spec: Any, causal: bool = True,
                                 elem_bytes: int = 2) -> RegistryKey:
    """Key for a flash-attention schedule ranking."""
    from repro.core.cost_model import COST_MODEL_VERSION
    problem = {"b": b, "hq": hq, "hkv": hkv, "s": s, "d": d,
               "causal": bool(causal), "elem_bytes": elem_bytes}
    return RegistryKey.make("flash_attention_schedule", problem,
                            fingerprint(spec), COST_MODEL_VERSION)


def decode_attention_schedule_key(b: int, hq: int, hkv: int, s: int,
                                  d: int, spec: Any, elem_bytes: int = 2,
                                  ) -> RegistryKey:
    """Key for a decode-attention schedule ranking."""
    from repro.core.cost_model import COST_MODEL_VERSION
    problem = {"b": b, "hq": hq, "hkv": hkv, "s": s, "d": d,
               "elem_bytes": elem_bytes}
    return RegistryKey.make("decode_attention_schedule", problem,
                            fingerprint(spec), COST_MODEL_VERSION)


def ssm_scan_schedule_key(bt: int, seq: int, di: int, n: int, spec: Any,
                          elem_bytes: int = 2) -> RegistryKey:
    """Key for an SSM-scan schedule ranking."""
    from repro.core.cost_model import COST_MODEL_VERSION
    problem = {"bt": bt, "seq": seq, "di": di, "n": n,
               "elem_bytes": elem_bytes}
    return RegistryKey.make("ssm_scan_schedule", problem,
                            fingerprint(spec), COST_MODEL_VERSION)


def quantize_density(density: float, steps: int = 16) -> int:
    """Block density quantised to a 1/``steps`` grid (an int numerator),
    so sparse-conv registry keys stay a finite, canonical-JSON-stable
    space instead of keying on raw floats."""
    return max(0, min(steps, int(round(float(density) * steps))))


def sparse_conv_schedule_key(layer: Any, density: float, spec: Any,
                             elem_bytes: int = 2) -> RegistryKey:
    """Key for a block-sparse conv schedule ranking."""
    from repro.core.cost_model import COST_MODEL_VERSION
    problem = conv_problem(layer, elem_bytes)
    problem["density_16"] = quantize_density(density)
    return RegistryKey.make("sparse_conv_schedule", problem,
                            fingerprint(spec), COST_MODEL_VERSION)


__all__ = [
    "SCHEMA_VERSION", "RegistryKey", "TuningRecord", "TuningRegistry",
    "canonical_json", "fingerprint", "runtime_fingerprint",
    "schedule_to_dict", "schedule_from_dict", "cost_to_dict",
    "cost_from_dict", "conv_problem", "conv_layer_from_problem",
    "conv_schedule_key", "matmul_schedule_key", "conv_sweep_key",
    "ecm_sweep_key", "ecm_correction_key", "KIND_TIERS", "kind_tier",
    "flash_attention_schedule_key", "decode_attention_schedule_key",
    "ssm_scan_schedule_key", "sparse_conv_schedule_key",
    "quantize_density", "machine_seen_path", "load_machine_seen",
    "save_machine_seen",
]
