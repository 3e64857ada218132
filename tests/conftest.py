"""Test config: force JAX onto a virtual 8-device CPU mesh so the whole suite
(including multi-chip sharding tests) runs anywhere without a TPU — the
TPU-sim tier of the test strategy (SURVEY.md §4 porting implication (d))."""

import os

# the suite never touches an accelerator, whatever the caller's environment
# says: pin the CPU backend (env for child processes, config for this one)
# and ask it for 8 virtual devices before any backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# CI artifact mirror: when ci_check.sh sets SLOW_QUERY_LOG_FILE, every
# slow-query JSON line the suite's journeys emit (full span trees, tenant
# tags) lands in a file the workflow uploads on failure — a red fairness
# or tracing journey is then debuggable from the artifact alone.
_slow_log_path = os.environ.get("SLOW_QUERY_LOG_FILE")
if _slow_log_path:
    import logging as _logging

    _h = _logging.FileHandler(_slow_log_path, delay=True)
    _h.setFormatter(_logging.Formatter("%(message)s"))
    _logging.getLogger("weaviate_tpu.slowquery").addHandler(_h)


# -- graftsan: runtime concurrency sanitizers (weaviate_tpu/testing/
# -- sanitizers.py) -----------------------------------------------------------
# GRAFTSAN=1 (ci_check.sh exports it for the tier-1 stage) wires the
# lock-order + device-sync + thread-leak sanitizers under the whole suite:
# serving locks constructed after this point are wrapped in order-witnessing
# proxies, the device->host fetch points assert no index/shard lock is held,
# and every test is followed by a thread-snapshot diff. Unset (the default)
# nothing is constructed and nothing is patched — the suite runs exactly as
# before. An unbaselined violation fails the test that first triggered it.
from weaviate_tpu.testing import sanitizers as _sanitizers  # noqa: E402

_graftsan_enabled = _sanitizers.parse_graftsan(os.environ.get("GRAFTSAN"))


def pytest_configure(config):
    if _graftsan_enabled:
        _sanitizers.configure(_sanitizers.GraftSan(_graftsan_enabled))


@pytest.fixture(autouse=True)
def _graftsan_guard():
    san = _sanitizers.get_sanitizer()
    if san is None:
        yield
        return
    mark = san.mark()
    before = (san.thread_snapshot()
              if _sanitizers.THREAD_LEAK in san.enabled else None)
    yield
    failures = []
    for v in san.since(mark):
        failures.append(v.render())
    if before is not None:
        # the leak scan reports through san._report, so re-mark first and
        # collect what IT found (already-baselined leaks stay waived)
        leak_mark = san.mark()
        san.leaked_threads(before)
        for v in san.since(leak_mark):
            failures.append(v.render())
    if failures:
        pytest.fail("graftsan violation(s):\n" + "\n\n".join(failures),
                    pytrace=False)


def pytest_sessionfinish(session, exitstatus):
    _graftsan_sessionfinish(session, exitstatus)
    # post-hatch status: when the graftsan escape hatch just failed the
    # session, the summary artifacts must not stamp exit_status 0
    _summaries_sessionfinish(getattr(session, "exitstatus", exitstatus))


def _graftsan_sessionfinish(session, exitstatus):
    """CI artifact + escape hatch. Dumps the sanitizer's full report
    (violations with stacks, witnessed acquisition-order edges, registry)
    — ci_check.sh sets GRAFTSAN_REPORT_FILE under CI_ARTIFACT_DIR; render
    it with `python -m tools.graftsan --report <file>`. Then: a violation
    first witnessed OUTSIDE a test body (module/session fixture setup,
    session teardown) ran before any _graftsan_guard mark, so no test
    failed for it — and first-seen dedup means an identical in-test
    repeat only bumped its count. On an otherwise-green run those would
    ship invisibly (the CI report artifact only uploads on failure), so
    fail the session here instead."""
    import json as _json
    import sys as _sys

    san = _sanitizers.get_sanitizer()
    if san is None:
        return
    path = os.environ.get("GRAFTSAN_REPORT_FILE")
    if path:
        try:
            with open(path, "w") as f:
                _json.dump(san.report(), f, indent=1)
        except Exception:  # noqa: BLE001 — artifact dump must not fail the run
            pass
    if exitstatus == 0:
        escaped = san.violations()
        if escaped:
            print("\ngraftsan: unbaselined violation(s) witnessed outside "
                  "any test body (fixture setup/teardown?) — failing the "
                  "session:\n\n"
                  + "\n\n".join(v.render() for v in escaped),
                  file=_sys.stderr)
            session.exitstatus = 1


def _summaries_sessionfinish(exitstatus):
    """CI artifact: dump the perf-attribution window summaries AND the
    shadow-recall-auditor summaries of the Apps this session ran
    (monitoring/perf.py and monitoring/quality.py each stash final
    summaries at unconfigure) — ci_check.sh sets PERF_SUMMARY_FILE /
    QUALITY_SUMMARY_FILE under CI_ARTIFACT_DIR and the workflow uploads
    both in ci-failure-logs, so a red run's bundle carries the
    duty-cycle/ledger picture and the recall picture."""
    import importlib
    import json as _json

    for env_key, module, doc_key in (
            ("PERF_SUMMARY_FILE", "weaviate_tpu.monitoring.perf",
             "windows"),
            ("QUALITY_SUMMARY_FILE", "weaviate_tpu.monitoring.quality",
             "audits"),
            ("MEMORY_SUMMARY_FILE", "weaviate_tpu.monitoring.memory",
             "ledgers"),
            ("INCIDENTS_SUMMARY_FILE", "weaviate_tpu.monitoring.incidents",
             "journals"),
            ("CONTROL_SUMMARY_FILE", "weaviate_tpu.serving.controller",
             "planes")):
        path = os.environ.get(env_key)
        if not path:
            continue
        try:
            mod = importlib.import_module(module)
            summaries = mod.recent_summaries()
            if summaries:
                with open(path, "w") as f:
                    _json.dump({"exit_status": int(exitstatus),
                                doc_key: summaries}, f, indent=1)
        except Exception:  # noqa: BLE001 — artifact dump must not fail the run
            pass


@pytest.fixture
def rng():
    return np.random.default_rng(42)
