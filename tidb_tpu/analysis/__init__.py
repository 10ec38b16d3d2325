"""tidb-vet — the repo's static-analysis suite (ISSUE 7 seeded the AST
lint passes; ISSUE 9 grew the interprocedural dataflow family and the
jaxpr program auditor; ref: go vet / Bazel nogo keeping the reference's
1.29M-LoC concurrent codebase honest).

Three families:

  * AST lint passes (stdlib `ast`, zero deps), each motivated by a bug a
    past PR actually paid for — see ANALYZERS.md for the catalog:
      jit-purity       module-level jax constants / config toggles
      lock-discipline  `# guarded_by:` attributes accessed off-lock
      metrics          registration/label consistency (shares promparse
                       with tools/scrape_check.py)
      wire-parity      encode_*/decode_* symmetry in codec/wire.py
      failpoints       armed names resolve to real injection sites
      suppressions     stale `# vet: ignore[...]` markers (audited from
                       the full-suite run)
  * interprocedural dataflow passes (analysis/dataflow.py): an
    AST-derived project call graph + forward fact propagation —
      dataflow-snapshot      MVCC reads on the request path flow start_ts
      dataflow-backoff       retry loops consult a Backoffer budget,
                             request-path sleeps are sliced/clamped
      dataflow-error-escape  typed errors map to SQLError codes before
                             the session boundary (supersedes PR-7's
                             lexical error-taxonomy)
    plus the jaxpr program auditor (analysis/jaxaudit.py, pass
    `jax-audit`): the exec builder's catalog traced to closed jaxprs and
    walked for f64 leaks, host callbacks, vmap axis drift and
    closure-captured scalars.
  * lockwatch (analysis/lockwatch.py) — the runtime lockset / lock-order
    detector the chaos, PD and replication concurrency tests run under
    in tier-1.

Driver: `python tools/vet.py [--json]` — exit 0 clean, 1 on findings.
Results cache per file revision in `.vet_cache.json` (analysis/
vetcache.py); suppress a finding with an inline `# vet: ignore[<pass>]`
marker (the `suppressions` pass flags markers that rot).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from . import (
    dataflow,
    failpoints,
    guards,  # noqa: F401 — re-exported for lockwatch/tests
    jaxaudit,
    jit_purity,
    lock_discipline,
    metrics_lint,
    promparse,
    suppress_audit,
    wire_parity,
)
from .common import REPO, Finding, SourceFile, filter_suppressed, load_files, py_files
from .vetcache import VetCache


@dataclass
class PassSpec:
    """One analyzer: how to run it, what it scans, how it caches.

    kind: "file"   — findings are a pure function of ONE file (cache per
                     (pass, file revision), runs parallelize per file)
          "corpus" — findings need the whole scope at once (cache per
                     (pass, corpus digest))
          "plain"  — self-scoped, uncached (failpoints: its inputs span
                     tests/ and tools/, which aren't loaded here)
    """

    run: object  # callable(files) -> [Finding]
    roots: tuple
    kind: str
    mods: tuple = field(default_factory=tuple)  # implementation modules (cache key)
    salt: str = ""  # extra cache-key ingredient (e.g. jax version)
    live_files: bool = True  # live run receives the scope files; False =
    # the pass owns its live inputs (jax-audit traces the builder, and an
    # explicit file list means fixture mode) — roots then only scope the
    # cache digest


def _jax_salt() -> str:
    try:
        import jax

        return f"jax-{jax.__version__}"
    except Exception:  # noqa: BLE001
        return "jax-?"


# pass name -> spec; the scan roots encode each pass's blast radius (jit
# purity only matters where programs trace, wire parity at the codec
# seam, the dataflow passes across the whole package)
PASSES: dict[str, PassSpec] = {
    jit_purity.PASS: PassSpec(
        jit_purity.run,
        ("tidb_tpu/ops", "tidb_tpu/exec", "tidb_tpu/expr", "tidb_tpu/parallel"),
        "file", (jit_purity,)),
    lock_discipline.PASS: PassSpec(
        lock_discipline.run, ("tidb_tpu",), "file", (lock_discipline, guards)),
    metrics_lint.PASS: PassSpec(
        metrics_lint.run, ("tidb_tpu",), "corpus", (metrics_lint, promparse)),
    wire_parity.PASS: PassSpec(
        wire_parity.run, ("tidb_tpu/codec/wire.py",), "corpus", (wire_parity,)),
    failpoints.PASS: PassSpec(failpoints.run, (), "plain", (failpoints,)),
    dataflow.PASS_SNAPSHOT: PassSpec(
        dataflow.run_snapshot, ("tidb_tpu",), "corpus", (dataflow,)),
    dataflow.PASS_BACKOFF: PassSpec(
        dataflow.run_backoff, ("tidb_tpu",), "corpus", (dataflow,)),
    dataflow.PASS_ESCAPE: PassSpec(
        dataflow.run_escape, ("tidb_tpu",), "corpus", (dataflow,)),
    jaxaudit.PASS: PassSpec(
        jaxaudit.run, ("tidb_tpu",), "corpus", (jaxaudit,), salt=_jax_salt(),
        live_files=False),
}

# the suppressions auditor is driver-level: it needs every OTHER pass's
# pre-suppression findings, so it runs from run_all(), not standalone
SUPPRESSIONS = suppress_audit.PASS
ALL_PASS_NAMES = tuple(PASSES) + (SUPPRESSIONS,)


def _in_scope(sf: SourceFile, roots: tuple) -> bool:
    rel = sf.rel.replace(os.sep, "/")
    for r in roots:
        if rel == r or rel.startswith(r.rstrip("/") + "/"):
            return True
    return False


_POOL_WORKERS = min(8, (os.cpu_count() or 2))


def _load_tree(roots=("tidb_tpu",)) -> list[SourceFile]:
    """Parse the scan universe ONCE, in parallel — PR 7 re-loaded it per
    pass, which is where most of the old wall-clock went."""
    paths = py_files(*roots)
    with ThreadPoolExecutor(max_workers=_POOL_WORKERS) as pool:
        return list(pool.map(SourceFile.load, paths))


def _run_file_pass(name: str, spec: PassSpec, scope, cache: VetCache) -> list:
    psha = cache.pass_sha(*spec.mods)
    out: list = []
    misses: list = []
    for sf in scope:
        key = VetCache.file_key(name, psha, sf)
        hit = cache.get(key)
        if hit is None:
            misses.append((key, sf))
        else:
            out.extend(hit)
    if misses:
        with ThreadPoolExecutor(max_workers=_POOL_WORKERS) as pool:
            results = list(pool.map(lambda m: spec.run([m[1]]), misses))
        for (key, _sf), fnds in zip(misses, results):
            cache.put(key, fnds)
            out.extend(fnds)
    return out


def _run_corpus_pass(name: str, spec: PassSpec, scope, cache: VetCache) -> list:
    key = VetCache.corpus_key(name, cache.pass_sha(*spec.mods), scope, spec.salt)
    hit = cache.get(key)
    if hit is not None:
        return hit
    fnds = spec.run(scope) if (spec.roots and spec.live_files) else spec.run(None)
    cache.put(key, fnds)
    return fnds


def _run_live(name: str, spec: PassSpec, tree, cache: VetCache) -> list:
    """One pass over the live tree (pre-suppression findings)."""
    scope = [sf for sf in tree if _in_scope(sf, spec.roots)] if spec.roots else []
    if spec.kind == "file":
        return _run_file_pass(name, spec, scope, cache)
    if spec.kind == "corpus":
        return _run_corpus_pass(name, spec, scope, cache)
    return spec.run(None)


def run_pass(name: str, files=None) -> list:
    """Run one pass; `files` overrides the default scan roots (fixture
    testing). Suppression markers are honored either way."""
    if name == SUPPRESSIONS:
        raise ValueError(
            "the suppressions audit needs every other pass's verdict — "
            "it only runs from run_all() (or the vet CLI without --only)")
    if files is not None:
        findings = PASSES[name].run(files)
        return filter_suppressed(findings, {sf.rel: sf for sf in files})
    return run_only([name])


def run_only(names, cache: VetCache | None = None) -> list:
    """A subset of passes over the live tree — ONE shared parse and the
    same per-revision cache as run_all (the `--only` inner loop while
    fixing one pass's findings should not pay a cold run each time).
    The stale-suppression audit needs every pass's verdict, so it only
    rides full runs."""
    if cache is None:
        cache = VetCache()
    tree = _load_tree(("tidb_tpu",))
    by_rel = {sf.rel: sf for sf in tree}
    out: list = []
    for name in names:
        out.extend(filter_suppressed(_run_live(name, PASSES[name], tree, cache), by_rel))
    cache.save()
    return sorted(out, key=lambda f: (f.path, f.line, f.passname))


def run_all(cache: VetCache | None = None) -> list:
    """Every pass over its default scope — shared parse, per-revision
    cache, suppression filtering with marker-usage tracking, and the
    stale-suppression audit over the result. Findings sorted by
    location."""
    if cache is None:
        cache = VetCache()
    tree = _load_tree(("tidb_tpu",))
    by_rel = {sf.rel: sf for sf in tree}
    used_markers: set = set()
    out: list = []
    for name, spec in PASSES.items():
        fnds = _run_live(name, spec, tree, cache)
        out.extend(filter_suppressed(fnds, by_rel, used_markers))
    out.extend(suppress_audit.audit(
        tree, used_markers, ran_passes=set(PASSES), known_passes=set(ALL_PASS_NAMES)))
    cache.save()
    return sorted(out, key=lambda f: (f.path, f.line, f.passname))
