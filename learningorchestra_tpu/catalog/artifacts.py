"""Typed binary artifact store.

The reference persists live Python objects between pipeline steps as
Keras SavedModel when possible and a ``dill`` blob otherwise, into
shared Docker volumes path-routed by artifact type
(binary_executor_image/utils.py:195-247). Capabilities preserved here:

- save/load any Python object by (name, type) — ``dill`` fallback;
- a *native* protocol for framework objects: anything exposing
  ``__lo_save__(dir)`` / classmethod ``__lo_load__(dir)`` (our JAX
  model handles use msgpack inside, not pickles);
- raw-bytes artifacts (e.g. the Explore service's plot PNGs,
  database_executor_image/utils.py:295-320);
- type-routed directory layout so every service reads every other
  service's artifacts (the reference mounts 6 volumes cross-service,
  docker-compose.yml:309-315 — here it is one tree).
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
from typing import Any, Dict, List, Optional, Tuple

import dill

from learningorchestra_tpu.observability import trace as obs_trace


class ArtifactNotFound(Exception):
    pass


_NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._ -]*$")


def validate_safe_name(name: str) -> str:
    """Reject path-traversal in artifact/collection names (these arrive
    from the REST API)."""
    if (not isinstance(name, str) or not _NAME_RE.match(name)
            or ".." in name or "/" in name or "\\" in name):
        raise ValueError(f"invalid artifact name: {name!r}")
    return name


def _validate_type(type_string: str) -> str:
    parts = type_string.split("/")
    if len(parts) != 2 or not all(_NAME_RE.match(p) for p in parts):
        raise ValueError(f"invalid artifact type: {type_string!r}")
    return type_string


def _dir_bytes(d: str) -> int:
    return sum(os.path.getsize(os.path.join(root, f))
               for root, _, files in os.walk(d) for f in files)


class ArtifactStore:
    def __init__(self, root: str):
        self._root = root
        os.makedirs(root, exist_ok=True)

    def _dir(self, name: str, type_string: str) -> str:
        # type strings look like "train/tensorflow"; use them directly
        # as the routing path (reference utils.py:230-247 routes by
        # type into /models, /binaries/<type>, /transform etc.).
        return os.path.join(
            self._root, _validate_type(type_string), validate_safe_name(name))

    def exists(self, name: str, type_string: str) -> bool:
        return os.path.exists(
            os.path.join(self._dir(name, type_string), "meta.json"))

    def find(self, name: str) -> Optional[str]:
        """Locate an artifact by name regardless of type; returns the
        type string (used by the universal readers and the lineage
        walk)."""
        for service_dir in sorted(os.listdir(self._root)):
            service_path = os.path.join(self._root, service_dir)
            if not os.path.isdir(service_path):
                continue
            for tool_dir in sorted(os.listdir(service_path)):
                candidate = os.path.join(service_path, tool_dir, name)
                if os.path.exists(os.path.join(candidate, "meta.json")):
                    return f"{service_dir}/{tool_dir}"
        return None

    # ------------------------------------------------------------------
    def save(self, obj: Any, name: str, type_string: str) -> str:
        from learningorchestra_tpu.services import faults

        faults.maybe_inject("artifact_save")
        d = self._dir(name, type_string)
        if os.path.isdir(d):
            shutil.rmtree(d)
        os.makedirs(d, exist_ok=True)
        meta: Dict[str, Any] = {"name": name, "type": type_string}
        if hasattr(obj, "__lo_save__"):
            payload_dir = os.path.join(d, "native")
            os.makedirs(payload_dir, exist_ok=True)
            obj.__lo_save__(payload_dir)
            meta.update({
                "kind": "native",
                "module": type(obj).__module__,
                "class": type(obj).__qualname__,
            })
        else:
            # dill fallback — covers sklearn estimators, tuples from
            # Function executions, arbitrary user objects (reference
            # utils.py:204-208).
            with open(os.path.join(d, "object.dill"), "wb") as f:
                dill.dump(obj, f)
            meta["kind"] = "dill"
        with open(os.path.join(d, "meta.json"), "w") as f:
            json.dump(meta, f)
        return d

    def stored_class(self, name: str, type_string: str):
        """The CLASS of a stored native artifact, resolved from
        meta.json without deserializing the object (validation wants
        the callable surface, not multi-GB weights on the request
        thread). Returns None for dill/bytes artifacts — callers fall
        back to a full load."""
        d = self._dir(name, type_string)
        meta_path = os.path.join(d, "meta.json")
        if not os.path.exists(meta_path):
            raise ArtifactNotFound(f"{type_string}/{name}")
        with open(meta_path) as f:
            meta = json.load(f)
        if meta.get("kind") != "native":
            return None
        module = importlib.import_module(meta["module"])
        cls = module
        for part in meta["class"].split("."):
            cls = getattr(cls, part)
        return cls

    def load(self, name: str, type_string: Optional[str] = None) -> Any:
        if type_string is None:
            type_string = self.find(name)
            if type_string is None:
                raise ArtifactNotFound(name)
        d = self._dir(name, type_string)
        meta_path = os.path.join(d, "meta.json")
        if not os.path.exists(meta_path):
            raise ArtifactNotFound(f"{type_string}/{name}")
        with open(meta_path) as f:
            meta = json.load(f)
        with obs_trace.span("artifactLoad", artifact=name,
                            bytes=_dir_bytes(d)):
            if meta["kind"] == "native":
                module = importlib.import_module(meta["module"])
                cls = module
                for part in meta["class"].split("."):
                    cls = getattr(cls, part)
                return cls.__lo_load__(os.path.join(d, "native"))
            elif meta["kind"] == "dill":
                with open(os.path.join(d, "object.dill"), "rb") as f:
                    return dill.load(f)
            elif meta["kind"] == "bytes":
                with open(os.path.join(d, meta["filename"]), "rb") as f:
                    return f.read()
            raise ValueError(f"unknown artifact kind {meta['kind']!r}")

    # ------------------------------------------------------------------
    def save_bytes(self, data: bytes, name: str, type_string: str,
                   filename: str = "payload.bin",
                   content_type: str = "application/octet-stream") -> str:
        d = self._dir(name, type_string)
        if os.path.isdir(d):
            shutil.rmtree(d)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, filename), "wb") as f:
            f.write(data)
        with open(os.path.join(d, "meta.json"), "w") as f:
            json.dump({"name": name, "type": type_string, "kind": "bytes",
                       "filename": filename,
                       "content_type": content_type}, f)
        return os.path.join(d, filename)

    def bytes_path(self, name: str, type_string: str) -> Tuple[str, str]:
        """Return (path, content_type) for a raw-bytes artifact (the
        Explore PNG GET endpoint, database_executor server.py:151-166).
        """
        d = self._dir(name, type_string)
        meta_path = os.path.join(d, "meta.json")
        if not os.path.exists(meta_path):
            raise ArtifactNotFound(f"{type_string}/{name}")
        with open(meta_path) as f:
            meta = json.load(f)
        if meta["kind"] != "bytes":
            raise ValueError(f"artifact {name} is not a bytes artifact")
        return os.path.join(d, meta["filename"]), meta.get(
            "content_type", "application/octet-stream")

    def delete(self, name: str, type_string: Optional[str] = None) -> bool:
        if type_string is None:
            type_string = self.find(name)
            if type_string is None:
                return False
        d = self._dir(name, type_string)
        if os.path.isdir(d):
            shutil.rmtree(d)
            return True
        return False

    def list(self, type_string: Optional[str] = None) -> List[str]:
        out = []
        if type_string is not None:
            d = os.path.join(self._root, type_string)
            if os.path.isdir(d):
                out = sorted(
                    n for n in os.listdir(d)
                    if os.path.exists(os.path.join(d, n, "meta.json")))
            return out
        for service_dir in sorted(os.listdir(self._root)):
            sp = os.path.join(self._root, service_dir)
            if not os.path.isdir(sp):
                continue
            for tool_dir in sorted(os.listdir(sp)):
                tp = os.path.join(sp, tool_dir)
                if not os.path.isdir(tp):
                    continue
                out.extend(
                    f"{service_dir}/{tool_dir}/{n}" for n in sorted(
                        os.listdir(tp))
                    if os.path.exists(os.path.join(tp, n, "meta.json")))
        return out
