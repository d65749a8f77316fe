"""What the chip bring-up changed around the device path: no fallback that
hides the device, one place for the compile cache, one chip detector, and
how ``chip_smoke.py`` fails without a chip. The script's full rehearsal
takes over a minute and lives in ``test_smoke_rehearsal.py``.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, *, cwd=None, env=None, timeout=300):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else [sys.executable, *code_or_args])
    full_env = {k: v for k, v in os.environ.items()
                if k not in ("JAX_COMPILATION_CACHE_DIR",)}
    full_env["PYTHONPATH"] = REPO
    for k, v in (env or {}).items():
        if v is None:
            full_env.pop(k, None)
        else:
            full_env[k] = v
    return subprocess.run(args, cwd=cwd or REPO, env=full_env, text=True,
                          capture_output=True, timeout=timeout)


def _last_json(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, f"no stdout; stderr:\n{proc.stderr[-3000:]}"
    return json.loads(lines[-1])


# ------------------------------------------------------------ compile cache
CACHE_PROBE = (
    "from ray_tpu.utils.compile_cache import enable_compile_cache\n"
    "import jax\n"
    "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
)


@pytest.mark.parametrize("case", ["pinned", "default", "cpu"])
def test_compile_cache_placement(case, tmp_path):
    """Variable set: the place is not overridden in code. Unset: the fixed
    path under the checkout, from any cwd. Pinned to the CPU backend: no
    cache. Wherever it is, it keeps every compile (jax's default: those
    over a second)."""
    checkout_cache = os.path.join(REPO, ".jax_cache")
    if case == "pinned":
        pinned = str(tmp_path / "operator_cache")
        out = _run(CACHE_PROBE, cwd=str(tmp_path), env={
            "JAX_COMPILATION_CACHE_DIR": pinned, "JAX_PLATFORMS": None})
        assert out.stdout.split() == [pinned, pinned, "0.0"], out.stderr[-2000:]
    elif case == "default":
        outs = [_run(CACHE_PROBE, cwd=cwd, env={"JAX_PLATFORMS": None})
                for cwd in (str(tmp_path), REPO)]
        for out in outs:
            assert out.stdout.split() == [checkout_cache, checkout_cache,
                                          "0.0"], out.stderr[-2000:]
    else:
        out = _run(CACHE_PROBE, cwd=str(tmp_path), env={"JAX_PLATFORMS": "cpu"})
        assert out.stdout.split() == ["None", "None", "0.0"], out.stderr[-2000:]


def test_compile_cache_counts_hits_and_misses():
    from ray_tpu.utils import compile_cache

    compile_cache.enable_compile_cache()
    before = compile_cache.compile_cache_stats()
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_event("/jax/compilation_cache/tasks_using_cache")
    after = compile_cache.compile_cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"] + 1


# ------------------------------------------------------------ chip detection
def test_local_init_counts_chips_without_importing_jax():
    out = _run(
        "import sys, ray_tpu\n"
        "ray_tpu.init(num_cpus=2)\n"
        "print('jax' in sys.modules, ray_tpu.cluster_resources().get('TPU'))\n"
        "ray_tpu.shutdown()",
        env={"RAY_TPU_FAKE_TPU_CHIPS": "2"})
    assert out.stdout.split() == ["False", "2.0"], out.stderr[-2000:]


@pytest.mark.parametrize("platforms,expected", [
    ("cpu", 0), (" CPU ", 0), ("tpu,cpu", 2), ("", 2), (None, 2)])
def test_detect_num_chips_honours_a_cpu_pin(monkeypatch, platforms, expected):
    from ray_tpu.core import accelerators

    monkeypatch.delenv(accelerators.FAKE_CHIPS_ENV, raising=False)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(accelerators.glob, "glob",
                        lambda pat: ["/dev/accel0", "/dev/accel1"])
    assert accelerators.detect_num_chips() == expected


# ------------------------------------------------- no fallback on the device path
def test_attention_rejects_an_unknown_impl():
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention

    q = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, q, q, impl="falsh")


def test_make_mesh_lets_create_device_mesh_raise(monkeypatch):
    from jax.experimental import mesh_utils

    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    def refuse(*a, **k):
        raise NotImplementedError("topology not mappable")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(NotImplementedError, match="not mappable"):
        make_mesh(MeshConfig(fsdp=8))


def test_paged_decode_builder_must_choose_the_kernel():
    from ray_tpu.models import paged_decode as pd
    from ray_tpu.models.llama import LlamaConfig

    with pytest.raises(TypeError):
        pd.make_paged_decode_fn(LlamaConfig.tiny(), 4, 16)
    assert not pd.paged_kernel_fits(LlamaConfig.tiny())
    assert pd.paged_kernel_fits(LlamaConfig.llama_1b())


@pytest.fixture
def tiny_engine():
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(
        LlamaConfig.tiny(remat=None, attention_impl="reference"),
        num_slots=2, decode_chunk=2, max_seq_len=64, page_size=16)
    yield engine
    engine.stop()


@pytest.mark.parametrize("program", ["_decode", "_prefill"])
def test_engine_fails_requests_with_the_step_exception(tiny_engine, program):
    """A program the chip's compiler refuses must reach the caller as that
    error, at once: not as a timeout after the loop logged and retried."""
    engine = tiny_engine
    assert engine.decode_attention == "gather"  # CPU: decided at build time
    assert engine.generate([1, 2, 3], max_tokens=2)["tokens"]

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    setattr(engine, program, refuse)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        engine.generate([1, 2, 3], max_tokens=4, timeout=30)
    assert time.monotonic() - t0 < 10
    # the engine is down and says why, to blocking and streaming callers
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        engine.generate([4, 5], max_tokens=2, timeout=30)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        list(engine.generate_stream([4, 5], max_tokens=2, timeout=30))
    engine._thread.join(timeout=10)  # it answers the callers, then ends
    assert not engine._thread.is_alive()


def test_engine_decode_program_text_names_the_path(tiny_engine):
    assert "tpu_custom_call" not in tiny_engine.decode_program_text()
    assert tiny_engine.stats()["decode_attention"] == "gather"


def test_native_build_failure_is_logged_not_silent(monkeypatch, caplog):
    from ray_tpu import _native

    def no_make(*a, **k):
        raise FileNotFoundError("make")

    monkeypatch.setattr(_native.subprocess, "run", no_make)
    with caplog.at_level("WARNING"):
        assert _native._try_build(force=True) is False
    assert "librtpu_native.so not built" in caplog.text


# ------------------------------------------------------------------ serve
def test_controller_keeps_a_replica_that_is_still_constructing(ray_tpu_local):
    """A TPU replica opens the chip and loads a model in its constructor.
    The health check must not drop it (and start a second one that can never
    get the chip) just because it does not answer yet."""
    from ray_tpu import serve

    class SlowStart:
        def __init__(self):
            time.sleep(3.0)

        def __call__(self, request=None):
            return "up"

    serve.start(http_port=0)
    try:
        handle = serve.run(serve.deployment(SlowStart, name="slow").bind(),
                           name="slow", http_port=0, timeout=60)
        assert handle.remote().result(timeout=30) == "up"
        status = serve.status()["slow"]
        assert status["running_replicas"] == 1
        # replica ids count up from 0: a second start would have made #1
        assert status["replica_stats"][0]["replica_id"] == "slow#0"
    finally:
        serve.shutdown()


# --------------------------------------------------------------- the scripts
@pytest.mark.parametrize("script,env", [
    ("chip_smoke.py", {"JAX_PLATFORMS": "cpu"}),
])
def test_scripts_fail_without_a_chip(script, env):
    out = _run([script], env=env, timeout=300)
    assert out.returncode != 0
    last = _last_json(out)
    assert last["ok"] is False and last["error"]
    assert "tpu" not in json.dumps(last.get("device", {}))


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(tmp_path), text=True,
        capture_output=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0
    assert _last_json(out)["ok"] is False
