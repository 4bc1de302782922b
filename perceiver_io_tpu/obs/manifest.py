"""Run manifests: the provenance record emitted alongside every artifact.

A bench JSON or chaos report is only comparable across PRs if you know what
produced it — which commit, which jax/jaxlib, which device fleet, which
config. ``build_run_manifest`` collects that (every probe individually
guarded: a missing git binary or an uninitialized backend degrades a field to
None, never fails the artifact), and ``write_run_manifest`` drops it next to
the artifact as ``<artifact stem>.manifest.json``. The schema-version map
names every artifact format this repo writes, so a reader can refuse
mismatched files loudly instead of misparsing them quietly
(docs/observability.md).
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
import time
from typing import Dict, Optional

MANIFEST_SCHEMA = "run-manifest/v1"

# every artifact schema the repo currently writes, in one place
ARTIFACT_SCHEMAS = {
    "serving_metrics": "serving-metrics/v13",
    "train_metrics": "train-metrics/v1",
    "chrome_trace": "chrome-trace/v1",
    "telemetry_summary": "telemetry-summary/v2",
    "request_journal": "request-journal/v1",
    "run_manifest": MANIFEST_SCHEMA,
}


def _git_sha() -> Optional[str]:
    try:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        out = subprocess.run(
            ["git", "-C", repo, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def _jax_versions() -> Dict[str, Optional[str]]:
    versions: Dict[str, Optional[str]] = {"jax": None, "jaxlib": None}
    try:
        import jax

        versions["jax"] = jax.__version__
    except Exception:
        pass
    try:
        import jaxlib

        versions["jaxlib"] = jaxlib.__version__
    except Exception:
        pass
    return versions


def _devices() -> Dict:
    try:
        import jax

        devices = jax.devices()
        return {
            "backend": jax.default_backend(),
            "count": len(devices),
            "kinds": sorted({d.device_kind for d in devices}),
        }
    except Exception:
        return {"backend": None, "count": None, "kinds": None}


def _jsonable(obj):
    """Best-effort plain-JSON projection of a config object (dataclass,
    namespace, dict, argparse.Namespace); non-encodable leaves become repr."""
    if obj is None:
        return None
    if hasattr(obj, "__dataclass_fields__"):
        import dataclasses

        try:
            obj = dataclasses.asdict(obj)
        except Exception:
            # asdict DEEP-COPIES field values and raises on non-picklable
            # ones (locks, generators, recorder objects) — degrade to the
            # shallow field dict; unencodable leaves still fall to repr below
            obj = dict(vars(obj))
    elif hasattr(obj, "__dict__") and not isinstance(obj, dict):
        obj = dict(vars(obj))
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        if isinstance(obj, dict):
            return {str(k): _jsonable(v) for k, v in obj.items()}
        return repr(obj)


def build_run_manifest(config=None, extra: Optional[Dict] = None) -> Dict:
    """Provenance dict: git sha, jax/jaxlib versions, device kind/count,
    python/platform, the producing config, and the artifact schema map."""
    manifest = {
        "schema": MANIFEST_SCHEMA,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
        "versions": {
            **_jax_versions(),
            "python": sys.version.split()[0],
        },
        "platform": platform.platform(),
        "devices": _devices(),
        "config": _jsonable(config),
        "artifact_schemas": dict(ARTIFACT_SCHEMAS),
    }
    if extra:
        manifest.update(_jsonable(extra) or {})
    return manifest


def manifest_path_for(artifact_path: str) -> str:
    stem, _ = os.path.splitext(artifact_path)
    return stem + ".manifest.json"


def write_run_manifest(artifact_path: str, config=None, extra: Optional[Dict] = None) -> str:
    """Write the manifest beside ``artifact_path`` (atomically, through the
    one audited sidecar-write path); returns the manifest path."""
    from perceiver_io_tpu.training.checkpoint import atomic_write_json

    path = manifest_path_for(artifact_path)
    atomic_write_json(path, build_run_manifest(config=config, extra=extra), indent=1)
    return path
