"""C++ client API integration (reference: cpp/ worker API,
cpp/include/ray/api.h:112-124 Task(F)/actor creation +
global_state_accessor): builds cpp/demo against the native msgpack-RPC
protocol and runs it against a live cluster — KV roundtrip, node/state
queries, a chunked 1MB object put/get through the agent, and the xlang
task/actor frontend (C++ submits by "xlang:<module>:<qualname>"
descriptor, a PYTHON worker executes, C++ fetches the msgpack result;
remote exceptions propagate as C++ exceptions)."""

import os
import shutil
import subprocess

import pytest

import ray_tpu
from ray_tpu.cluster import Cluster

CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "cpp")


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_cpp_client_demo_roundtrip():
    build = subprocess.run(["make", "-C", CPP_DIR], capture_output=True,
                           text=True, timeout=120)
    assert build.returncode == 0, build.stderr
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        host, port = c.gcs_address.rsplit(":", 1)
        out = subprocess.run([os.path.join(CPP_DIR, "demo"), host, port],
                             capture_output=True, text=True, timeout=180)
        assert "CPP-DEMO-OK" in out.stdout, (out.stdout, out.stderr)
        assert "object roundtrip ok" in out.stdout
        # xlang task/actor frontend: Python worker ran operator.add and a
        # collections.Counter actor on behalf of the C++ driver
        assert "task roundtrip ok (operator.add -> 42)" in out.stdout
        assert "task error propagation ok" in out.stdout
        assert "actor roundtrip ok (Counter.total -> 3)" in out.stdout
    finally:
        c.shutdown()


@pytest.mark.timeout_s(150)  # 1 s beside 12 CPU burners; the demo has 90 s
@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_cpp_put_python_get_interop():
    """An object stored by the C++ client is a first-class object: Python
    drivers see it in the GCS directory and agents serve it."""
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        host, port = c.gcs_address.rsplit(":", 1)
        subprocess.run([os.path.join(CPP_DIR, "demo"), host, port],
                       capture_output=True, text=True, timeout=90)
        ray_tpu.init(address=c.gcs_address, log_to_driver=False)
        from ray_tpu.core.worker import global_worker

        rt = global_worker().runtime
        objs = rt.gcs.call("list_objects")
        assert any(o["size"] > 1_000_000 for o in objs), objs
    finally:
        ray_tpu.shutdown()
        c.shutdown()
