"""Registry type-collision lint (model-health PR satellite).

The registry raises TypeError when one metric name is requested under two
instrument types — but only at RUNTIME, on the first colliding call path.
A counter registered in train_step.py and a same-named gauge in a tool
nobody ran in CI ships broken. This lint makes the collision a tier-1
import-time failure:

* every module under ``paddle_tpu`` must import cleanly (the walk is also
  the package-wide smoke test the health plane's lazy imports rely on);
* a source scan over the whole package (plus ``tools/``, which registers
  against the same live registries) collects every literal
  ``counter("...")`` / ``gauge("...")`` / ``histogram("...")`` name —
  including the static prefix of f-string names — and asserts no name is
  claimed by two instrument types, nor any dynamic-prefix family by a
  different type than its static kin.
"""
import importlib
import os
import pkgutil
import re

import pytest

import paddle_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# modules whose import has process-global side effects unsuitable for an
# indiscriminate walk — keep the lint honest by adding a reason next to any
# future entry
_SKIP = {
    # C-ABI shared libraries loaded via ctypes, not Python extensions:
    # pkgutil lists them but `import` rightly rejects them
    "paddle_tpu.inference.capi.libpaddle_inference_c",
    "paddle_tpu.inference.native.libpaddle_native_runtime",
}


def _walk_modules():
    out = []
    for mod in pkgutil.walk_packages(paddle_tpu.__path__,
                                     prefix="paddle_tpu."):
        if mod.name in _SKIP or mod.name.endswith(".__main__"):
            continue  # importing __main__ IS running the CLI, by design
        out.append(mod.name)
    return sorted(out)


def test_every_module_imports():
    failures = {}
    for name in _walk_modules():
        try:
            importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 — collecting, not handling
            failures[name] = f"{type(e).__name__}: {e}"
    assert not failures, f"modules failed to import: {failures}"


_CALL = re.compile(r'\.(counter|gauge|histogram)\(\s*(f?)"([^"\n]+)"')


def _scan_sources():
    """{metric name or f-string prefix: {instrument types}} over the whole
    registering surface (package + tools)."""
    roots = [os.path.join(REPO, "paddle_tpu"), os.path.join(REPO, "tools")]
    claims = {}
    for root in roots:
        paths = [os.path.join(dp, f) for dp, _, fs in os.walk(root)
                 for f in fs if f.endswith(".py")]
        for path in paths:
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            for typ, is_f, name in _CALL.findall(src):
                if is_f and "{" in name:
                    name = name.split("{", 1)[0]  # static prefix of dynamic
                claims.setdefault(name, {}).setdefault(typ, []).append(
                    os.path.relpath(path, REPO))
    return claims


def test_no_metric_name_under_two_instrument_types():
    claims = _scan_sources()
    assert len(claims) > 30, "source scan found implausibly few metrics"
    bad = {n: {t: sorted(set(fs)) for t, fs in by.items()}
           for n, by in claims.items() if len(by) > 1}
    assert not bad, (
        f"metric names registered under two instrument types (the registry "
        f"would raise TypeError on the first colliding call path): {bad}")
    # dynamic families must not collide with a DIFFERENTLY-typed static kin:
    # f"health/nan_trips.{g}" (counter) vs a hypothetical
    # gauge("health/nan_trips.total") slips past the exact-name check above
    names = sorted(claims)
    for i, prefix in enumerate(names):
        if not prefix.endswith((".", "/", "_")):
            continue
        ptypes = set(claims[prefix])
        for other in names:
            if other != prefix and other.startswith(prefix):
                otypes = set(claims[other])
                assert otypes <= ptypes or ptypes <= otypes, (
                    f"dynamic family {prefix!r} ({ptypes}) collides with "
                    f"{other!r} ({otypes})")


def test_live_registry_rejects_type_collisions():
    """The runtime guarantee the lint leans on: same name + different type
    is a loud TypeError on the live registry, never a silent shadow."""
    from paddle_tpu import monitor
    r = monitor.Registry()
    r.counter("lint/x").inc()
    with pytest.raises(TypeError):
        r.gauge("lint/x")
    with pytest.raises(TypeError):
        r.histogram("lint/x")
