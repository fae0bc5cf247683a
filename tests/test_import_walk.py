"""Import-surface smoke: every repro.* module must import cleanly on a
single CPU device.  A missing subsystem (like the repro.dist regression
this guards against) fails here in milliseconds instead of surfacing as a
wall of collection errors."""

import importlib
import os
import pkgutil


def _walk(package_name):
    pkg = importlib.import_module(package_name)
    names = [package_name]
    for info in pkgutil.walk_packages(pkg.__path__,
                                      prefix=package_name + "."):
        names.append(info.name)
    return names


def test_every_repro_module_imports():
    names = _walk("repro")
    assert len(names) > 50, f"suspiciously few modules found: {len(names)}"
    failures = {}
    # launch.dryrun sets XLA_FLAGS and JAX_PLATFORMS (subprocess entry
    # point); importing it here is safe since the backend is already
    # initialized, but the env mutation must not leak into later
    # subprocess-spawning tests.
    saved = {k: os.environ.get(k) for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
    try:
        for name in sorted(names):
            try:
                importlib.import_module(name)
            except Exception as e:  # noqa: BLE001 — collect all, report once
                failures[name] = f"{type(e).__name__}: {e}"
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert not failures, "\n".join(f"{k}: {v}" for k, v in failures.items())


def test_dist_package_exports():
    from repro.dist import (collective_matmul, compression, pipeline,
                            sharding)

    assert callable(sharding.param_spec)
    assert callable(sharding.use_ruleset)
    assert callable(compression.int8_roundtrip)
    assert callable(collective_matmul.ag_matmul)
    assert callable(pipeline.gpipe)
