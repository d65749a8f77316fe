"""rtpu-lint (tools/rtpulint) — per-pass fixtures + the repo-wide gate.

Each pass gets a pair of fixtures: a seeded violation it must catch and the
corrected form it must stay silent on. The gate test at the bottom runs the
real CLI over ray_tpu/ and fails the tier-1 suite on any unsuppressed,
unbaselined finding — the analyzer IS a test.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.rtpulint.core import (PASS_NAMES, ParsedFile, default_baseline_path,
                                 lint_paths, load_files)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_src(tmp_path, src, *, passes=None, name="mod.py", extra=None):
    """Lint one synthetic module in an isolated repo root."""
    files = {name: src}
    files.update(extra or {})
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                      passes=passes, with_evidence=False)


def _tokens(result):
    return {f.key_token for f in result.findings}


# --------------------------------------------------------------- rpc-drift

RPC_MODULE = """
    class Service:
        async def rpc_kv_put(self, key, value):
            return True

        async def rpc_kv_get(self, key):
            return None

        def start(self, server):
            server.register_object(self)

    class Client:
        async def go(self, peer):
            await peer.call("kv_put", key="a", value=1)
            await peer.call("kv_get", key="a", timeout=5.0)
"""


def test_rpc_drift_clean(tmp_path):
    result = _lint_src(tmp_path, RPC_MODULE, passes=["rpc-drift"])
    assert result.ok, [f.render() for f in result.findings]


def test_rpc_drift_unresolved_call(tmp_path):
    src = RPC_MODULE.replace('peer.call("kv_put"', 'peer.call("kv_putt"')
    result = _lint_src(tmp_path, src, passes=["rpc-drift"])
    assert "call:kv_putt" in _tokens(result)


def test_rpc_drift_unused_handler(tmp_path):
    src = RPC_MODULE.replace('await peer.call("kv_get", key="a", timeout=5.0)',
                             "pass")
    result = _lint_src(tmp_path, src, passes=["rpc-drift"])
    assert "unused:kv_get" in _tokens(result)


def test_rpc_drift_kwarg_drift(tmp_path):
    src = RPC_MODULE.replace('peer.call("kv_put", key="a", value=1)',
                             'peer.call("kv_put", key="a", val=1)')
    result = _lint_src(tmp_path, src, passes=["rpc-drift"])
    assert "kwarg:kv_put:val" in _tokens(result)
    # `timeout` is consumed client-side and must never be flagged
    assert not any(t.startswith("kwarg:kv_get") for t in _tokens(result))


def test_rpc_drift_actor_methods_not_handlers(tmp_path):
    # rpc_* methods in a module that never register_object()s ride the actor
    # plane (e.g. serve ProxyActor.rpc_address) — not RPC handlers
    src = """
        class ProxyActor:
            def rpc_address(self):
                return ("h", 1)
    """
    result = _lint_src(tmp_path, src, passes=["rpc-drift"])
    assert result.ok, [f.render() for f in result.findings]


def test_rpc_drift_conditional_and_forwarded_methods(tmp_path):
    src = """
        class S:
            async def rpc_up(self):
                return 1

            async def rpc_down(self):
                return 0

            async def rpc_probe(self):
                return 2

            def start(self, server):
                server.register_object(self)

        class C:
            async def flip(self, peer, ok):
                await peer.call("up" if ok else "down")

            async def _fan(self, method):
                return await self.peer.call(method)

            async def go(self):
                return await self._fan("probe")
    """
    result = _lint_src(tmp_path, src, passes=["rpc-drift"])
    assert result.ok, [f.render() for f in result.findings]


# ------------------------------------------------------------- orphan-task

def test_orphan_task_caught_and_fixed(tmp_path):
    bad = """
        import asyncio

        async def go():
            asyncio.ensure_future(work())
            asyncio.get_event_loop().create_task(work())
    """
    result = _lint_src(tmp_path, bad, passes=["orphan-task"])
    assert len(result.findings) == 2

    good = """
        import asyncio
        from ray_tpu.core.rpc import spawn

        async def go(self):
            spawn(work())
            self._task = asyncio.ensure_future(work())
    """
    result = _lint_src(tmp_path, good, passes=["orphan-task"])
    assert result.ok, [f.render() for f in result.findings]


# ------------------------------------------------------------ loop-blocker

def test_loop_blocker_caught_and_fixed(tmp_path):
    bad = """
        import time, subprocess

        async def go():
            time.sleep(1.0)
            subprocess.run(["ls"])
    """
    result = _lint_src(tmp_path, bad, passes=["loop-blocker"])
    assert len(result.findings) == 2

    good = """
        import asyncio, time

        async def go():
            await asyncio.sleep(1.0)

        def sync_helper():
            time.sleep(1.0)  # fine: not on the event loop
    """
    result = _lint_src(tmp_path, good, passes=["loop-blocker"])
    assert result.ok, [f.render() for f in result.findings]


# -------------------------------------------------------------------- race

def test_race_straddle_caught_and_fixed(tmp_path):
    bad = """
        class A:
            async def go(self, key):
                self.pending[key] = 1
                await self.flush()
                self.pending.pop(key)
    """
    result = _lint_src(tmp_path, bad, passes=["race"])
    assert any(t.startswith("straddle:go:pending") for t in _tokens(result))

    good = """
        class A:
            async def go(self, key):
                async with self._lock:
                    self.pending[key] = 1
                    await self.flush()
                    self.pending.pop(key)

            async def branches(self, key, add):
                if add:
                    self.pending[key] = 1
                    return 1
                await self.flush()
                self.pending.pop(key, None)
    """
    result = _lint_src(tmp_path, good, passes=["race"])
    assert result.ok, [f.render() for f in result.findings]


def test_race_lock_across_remote_call(tmp_path):
    bad = """
        class A:
            async def go(self):
                async with self._lock:
                    await self.gcs.call("lookup_object", object_id="x")
    """
    result = _lint_src(tmp_path, bad, passes=["race"])
    assert any(t.startswith("lock-call:go") for t in _tokens(result))

    good = """
        class A:
            async def go(self):
                async with self._lock:
                    await self._local_refresh()
                rec = await self.gcs.call("lookup_object", object_id="x")
                return rec
    """
    result = _lint_src(tmp_path, good, passes=["race"])
    assert result.ok, [f.render() for f in result.findings]


# ---------------------------------------------------------------- env-flag

def test_env_flag_violations_and_fixed(tmp_path):
    bad = """
        import os

        def f():
            return os.environ.get("RTPU_SECRET_KNOB", "0")
    """
    result = _lint_src(tmp_path / "bad", bad, passes=["env-flag"])
    tokens = _tokens(result)
    assert {"outside:RTPU_SECRET_KNOB", "undeclared:RTPU_SECRET_KNOB",
            "undocumented:RTPU_SECRET_KNOB"} <= tokens

    good = """
        import os

        def knob_enabled():
            return os.environ.get("RTPU_KNOB", "0") == "1"
    """
    result = _lint_src(tmp_path / "good", good, passes=["env-flag"],
                       name="core/config.py",
                       extra={"README.md": "Set `RTPU_KNOB=1` to enable.\n"})
    assert result.ok, [f.render() for f in result.findings]


# ---------------------------------------------- suppressions and baseline

def test_inline_suppression_and_trailing_prose(tmp_path):
    src = """
        import time

        async def go():
            time.sleep(0.1)  # rtpulint: disable=loop-blocker
            # rtpulint: disable=loop-blocker -- thread-hosted loop, safe
            time.sleep(0.2)
            time.sleep(0.3)
    """
    result = _lint_src(tmp_path, src, passes=["loop-blocker"])
    assert len(result.findings) == 1          # only the 0.3 sleep survives
    assert result.suppressed == 2


def test_file_suppression(tmp_path):
    src = """
        # rtpulint: disable-file=loop-blocker
        import time

        async def go():
            time.sleep(0.1)
    """
    result = _lint_src(tmp_path, src, passes=["loop-blocker"])
    assert result.ok and result.suppressed == 1


def test_suppression_inside_string_is_ignored():
    pf = ParsedFile("<mem>", "mem.py",
                    's = "# rtpulint: disable=race"\n')
    assert not pf.is_suppressed(1, "race")


def test_baseline_hides_triaged_findings(tmp_path):
    src = """
        import time

        async def go():
            time.sleep(0.1)
    """
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent(src))
    first = lint_paths([str(mod)], repo_root=str(tmp_path),
                       passes=["loop-blocker"], with_evidence=False)
    assert len(first.findings) == 1
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps(
        {"findings": {first.findings[0].key: "triaged"}}))
    second = lint_paths([str(mod)], repo_root=str(tmp_path),
                        baseline_path=str(baseline),
                        passes=["loop-blocker"], with_evidence=False)
    assert second.ok and second.baselined == 1


# ---------------------------------------------------------------- CLI + gate

def _run_cli(*argv, cwd=REPO_ROOT):
    return subprocess.run([sys.executable, "-m", "tools.rtpulint", *argv],
                          cwd=cwd, capture_output=True, text=True, timeout=120)


def test_cli_json_and_exit_code(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def go():\n    time.sleep(1)\n")
    proc = _run_cli(str(bad), "--no-baseline", "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert not report["ok"]
    assert report["findings"][0]["pass"] == "loop-blocker"
    assert sorted(f["pass"] for f in report["findings"]) == ["loop-blocker"]


def test_cli_pass_selection(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def go():\n    time.sleep(1)\n")
    proc = _run_cli(str(bad), "--no-baseline", "--pass", "race")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_repo_gate_zero_findings():
    """THE gate: `python -m tools.rtpulint ray_tpu/` must exit 0 — every
    finding in the tree is either fixed, inline-suppressed with a reason,
    or triaged into the checked-in baseline."""
    proc = _run_cli("ray_tpu/")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_env_flag_baseline_is_empty():
    """The env-flag surface is fully reconciled: no triaged legacy entries."""
    with open(default_baseline_path(), "r", encoding="utf-8") as fh:
        entries = json.load(fh).get("findings", {})
    assert not [k for k in entries if "::env-flag::" in k], entries


def test_every_core_call_site_resolves():
    """100% of string-literal call() sites in ray_tpu/core/ resolve to a
    live handler (acceptance criterion, asserted directly on the collector
    so a future baseline entry cannot mask a regression)."""
    from tools.rtpulint.passes.rpc_drift import (BUILTIN_HANDLERS,
                                                 _collect_calls,
                                                 _collect_forwarders,
                                                 _collect_handlers)

    files = load_files([os.path.join(REPO_ROOT, "ray_tpu")], REPO_ROOT)
    handlers = {h.name for h in _collect_handlers(files)}
    handlers |= set(BUILTIN_HANDLERS)
    sites = _collect_calls(files, _collect_forwarders(files))
    unresolved = [(s.path, s.line, s.method) for s in sites
                  if s.path.startswith("ray_tpu/core/")
                  and s.method not in handlers]
    assert not unresolved, unresolved


def test_every_setting_is_declared_once():
    """The registry is built into a dict: of two entries with one name the
    later silently wins, and the earlier's documented default never applies
    (``max_lineage_bytes`` was declared at 8 MiB and at 512 MiB)."""
    from collections import Counter

    from ray_tpu.core.config import _DEFINITIONS

    twice = [n for n, k in Counter(d[0] for d in _DEFINITIONS).items()
             if k > 1]
    assert not twice, twice


def test_pass_registry_complete():
    from tools.rtpulint.passes import ALL_PASSES

    assert tuple(ALL_PASSES) == PASS_NAMES
