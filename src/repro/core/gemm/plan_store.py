"""Persistent on-disk plan cache for the measured auto-tuner.

The paper's auto-tuning is a *closed loop*: the CMR model proposes, the
hardware disposes, and the winner is remembered so the search never reruns
for a shape the device has already answered.  This module is the memory —
a JSON file of measured-winner records keyed by

    (device kind, plan family, shape signature, dtype widths, placement
     request)

that the analytic planners (``tuner.plan_*``) consult *before* their
CMR argmin.  Records store only the decision (blocks, dim order, strategy)
plus provenance (measured/analytic times, timing engine); the analytic
estimate is recomputed at lookup so a cached plan always carries a fresh
``PlanEstimate`` and is re-validated against the VMEM budget — a cache can
suggest, it can never force a shape-invalid tiling.

Device-kind gating: a store file created on one device kind (say
``tpu_v5e``) is ignored wholesale on another (``cpu``) — measured times do
not transfer.  Corrupt or schema-mismatched files are ignored gracefully
(the loop falls back to pure analytic planning), never raised through the
planners.

The file also carries the **calibration** block fitted by
``autotune.calibrate``: the effective achievable-flops fraction and HBM
bandwidth fraction of the device, so *unmeasured* shapes plan against
corrected constants too.

Process-global store: ``get_store()``; auto-loads ``$REPRO_PLAN_CACHE`` on
first use.  This module stays jax-light (jax imported lazily only to read
the device kind) so ``tuner`` can import it without cycles.
"""
from __future__ import annotations

import json
import os
import tempfile
from dataclasses import dataclass, field

from ...runtime.chaos import fire as _chaos_fire

SCHEMA_VERSION = 1
ENV_VAR = "REPRO_PLAN_CACHE"

# Fields a record may carry.  Only "blocks" is mandatory; everything else is
# provenance or placement/edge/fusion detail.
_RECORD_KEYS = frozenset({
    "bm", "bn", "bk", "nsplit", "dim_order", "strategy", "schedule", "edge",
    "fuse", "t_measured_us", "t_analytic_us", "t_model_us", "engine", "mode",
})


def device_kind() -> str:
    """Canonical device kind of the timing device: the ``TpuSpec`` name of
    a TPU ("tpu_v5e" for a chip reporting "TPU v5 lite"; an unknown TPU
    kind raises), else the backend's own kind ("cpu")."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        from .cmr import spec_for_kind
        return spec_for_kind(dev.device_kind).name
    return str(dev.device_kind).strip().lower().replace(" ", "_")


def shape_key(family: str, dims: tuple, in_bytes: int, out_bytes: int,
              num_shards: int = 1, extra: str = "") -> str:
    """Canonical store key: family + shape signature + dtype widths +
    placement request.  ``dims`` is the family's positional shape tuple
    ((m,k,n) dense, (g,m,k,n) batched, (g,total,k,n) ragged); ``extra``
    carries family variants (shared operand, ragged axis)."""
    d = "x".join(str(int(x)) for x in dims)
    key = f"{family}|{d}|ib{int(in_bytes)}|ob{int(out_bytes)}"
    if extra:
        key += f"|{extra}"
    if num_shards > 1:
        key += f"|shards{int(num_shards)}"
    return key


@dataclass
class Calibration:
    """Fitted effective-hardware constants (fractions of the spec's peaks).

    ``flops_frac_int8`` is the separately-fitted achievable fraction of the
    narrow-dtype (int8) peak — the MXU's int8 path saturates differently
    from its float path, so one shared fraction would misprice whichever
    family was not measured.  ``None`` means "not fitted": the planners
    fall back to ``flops_frac`` for int8 shapes too."""
    flops_frac: float = 1.0     # achievable fraction of peak FLOP/s
    bw_frac: float = 1.0        # achievable fraction of peak HBM bandwidth
    ici_frac: float = 1.0       # achievable fraction of peak ICI bandwidth
    flops_frac_int8: float | None = None    # int8-peak fraction (optional)
    n_samples: int = 0
    engine: str = ""
    base_spec: str = ""

    def to_json(self) -> dict:
        d = {"flops_frac": self.flops_frac, "bw_frac": self.bw_frac,
             "ici_frac": self.ici_frac, "n_samples": self.n_samples,
             "engine": self.engine, "base_spec": self.base_spec}
        if self.flops_frac_int8 is not None:
            d["flops_frac_int8"] = self.flops_frac_int8
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Calibration":
        int8 = d.get("flops_frac_int8")
        return cls(flops_frac=float(d["flops_frac"]),
                   bw_frac=float(d["bw_frac"]),
                   ici_frac=float(d.get("ici_frac", 1.0)),
                   flops_frac_int8=None if int8 is None else float(int8),
                   n_samples=int(d.get("n_samples", 0)),
                   engine=str(d.get("engine", "")),
                   base_spec=str(d.get("base_spec", "")))


@dataclass
class PlanStore:
    """In-memory view of one persistent plan-cache file.

    ``quarantined`` maps record keys the static verifier rejected at load
    time to their violation codes — those shapes fall back to analytic
    planning, and the count is surfaced by ``tuner.plan_mode_stats`` and
    the serve warmup banner instead of being silently re-planned."""
    kind: str = ""                          # device kind the entries measure
    entries: dict = field(default_factory=dict)
    calibration: Calibration | None = None
    path: str | None = None                 # last load/save path
    quarantined: dict = field(default_factory=dict)
    lookups: int = 0                        # telemetry: lookup() calls
    hits: int = 0                           # telemetry: lookups that served

    def __len__(self) -> int:
        return len(self.entries)

    def lookup(self, key: str) -> dict | None:
        """Record for ``key`` if it was measured on the current device kind."""
        self.lookups += 1
        if not self.entries or self.kind != device_kind():
            return None
        rec = self.entries.get(key)
        if rec is not None:
            self.hits += 1
        return rec

    def put(self, key: str, record: dict) -> None:
        self.kind = self.kind or device_kind()
        self.entries[key] = {k: v for k, v in record.items()
                             if k in _RECORD_KEYS}

    def clear(self) -> None:
        self.entries.clear()
        self.quarantined.clear()
        self.calibration = None
        self.kind = ""
        self.lookups = 0
        self.hits = 0

    # -- persistence ------------------------------------------------------

    def load(self, path: str) -> int:
        """Merge entries from ``path``.  Returns the number of entries
        adopted; 0 (never an exception) for missing / corrupt / wrong-schema
        / wrong-device-kind files."""
        try:
            with open(path) as fp:
                blob = json.load(fp)
        except (OSError, ValueError):
            return 0
        if not isinstance(blob, dict) \
                or blob.get("schema") != SCHEMA_VERSION:
            return 0
        kind = blob.get("device_kind")
        if kind != device_kind():
            return 0        # measured elsewhere: times don't transfer
        entries = blob.get("entries")
        if not isinstance(entries, dict):
            return 0
        n = 0
        for key, rec in entries.items():
            if isinstance(rec, dict) and "bm" in rec:
                bad = _record_violations(key, rec)
                if bad:
                    # Contract-violating cached plans (the bk-clamp bug
                    # class, over-budget blocks, malformed keys) are
                    # quarantined, never served; the planners re-plan the
                    # shape analytically and telemetry counts the miss.
                    self.quarantined[key] = bad
                    continue
                self.put(key, rec)
                n += 1
        self.kind = kind
        cal = blob.get("calibration")
        if isinstance(cal, dict):
            try:
                self.calibration = Calibration.from_json(cal)
            except (KeyError, TypeError, ValueError):
                pass
        self.path = path
        return n

    def save(self, path: str | None = None) -> str:
        path = path or self.path
        if path is None:
            raise ValueError("no path: pass one or load() first")
        blob = {
            "schema": SCHEMA_VERSION,
            "device_kind": self.kind or device_kind(),
            "entries": self.entries,
        }
        if self.calibration is not None:
            blob["calibration"] = self.calibration.to_json()
        d = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(d, exist_ok=True)
        # Atomic replace so a crashed writer never leaves a torn file for
        # the graceful-degradation loader to (correctly, silently) reject.
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".plan_cache.")
        try:
            with os.fdopen(fd, "w") as fp:
                json.dump(blob, fp, indent=1, sort_keys=True)
                fp.flush()
                os.fsync(fp.fileno())
            _chaos_fire("plan_save_crash")
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.path = path
        return path


def _record_violations(key: str, rec: dict) -> list:
    """Error-severity static-contract violation codes for one cached record
    (the load-time quarantine gate).  Lazy import: the verifier package is
    a leaf, but keeping the store importable without it preserves the
    graceful-degradation promise of ``load``."""
    try:
        from ...analysis.contracts import check_record, errors
    except Exception:   # pragma: no cover - analysis ships with the repo
        return []
    return [v.code for v in errors(check_record(key, rec))]


_STORE = PlanStore()
_env_checked = False


def get_store() -> PlanStore:
    """The process-global store; loads ``$REPRO_PLAN_CACHE`` on first use."""
    global _env_checked
    if not _env_checked:
        _env_checked = True
        path = os.environ.get(ENV_VAR)
        if path:
            _STORE.load(path)
    return _STORE


def reset_store() -> None:
    """Drop all in-memory entries + calibration (the file is untouched).
    The ``$REPRO_PLAN_CACHE`` auto-load is NOT re-armed: a reset means an
    empty store until an explicit ``load`` — otherwise the very next
    ``get_store()`` would silently refill the "clean slate" from the env
    file (and a sweep started from reset would merge stale entries into
    whatever it saves)."""
    global _env_checked
    _env_checked = True
    _STORE.clear()
    _STORE.path = None
