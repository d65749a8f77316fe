"""One run's cluster: started before, gone after. The driver process never
initialises a jax backend; the chip belongs to the worker the agent leases."""

from __future__ import annotations

import os
import shutil
import sys
from typing import List

from benchmarks.harness import procs
from benchmarks.harness.manifest import ROOT


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for: no result."""


class BenchSession:
    def __init__(self, chips: int, workload: str):
        self.chips, self.workload = chips, workload
        self.token = None
        self.cluster = None
        self.left_behind: List[int] = []
        self.scratch = os.path.join(ROOT, ".bench_runs", workload)

    def __enter__(self):
        self.token = procs.mark_environment()
        # workers import ``benchmarks.*`` (the deployment, the train loop)
        os.environ["PYTHONPATH"] = os.pathsep.join(
            p for p in [ROOT, os.environ.get("PYTHONPATH", "")] if p)
        shutil.rmtree(self.scratch, ignore_errors=True)
        os.makedirs(self.scratch, exist_ok=True)
        import ray_tpu
        from ray_tpu.cluster import Cluster

        try:
            self.cluster = Cluster(
                initialize_head=True,
                head_node_args={"num_cpus": max(8, os.cpu_count() or 8)})
            os.environ["RAY_TPU_SESSION_DIR"] = self.cluster.session_dir
            ray_tpu.init(address=self.cluster.gcs_address)
            tpus = ray_tpu.cluster_resources().get("TPU", 0)
            if tpus < self.chips:
                raise NoChip(f"the node has {tpus} chip(s), the cell needs {self.chips}")
        except BaseException:
            self.__exit__(*sys.exc_info())
            raise
        return self

    def __exit__(self, *exc):
        import ray_tpu

        known = procs.snapshot(self.token)  # while they can still be found
        try:
            from ray_tpu import serve

            serve.shutdown()
        except Exception:  # noqa: BLE001 - teardown goes on
            pass
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if self.cluster is not None:
            try:
                self.cluster.shutdown()
            except Exception:  # noqa: BLE001
                pass
        self.left_behind = procs.reap_all(self.token, known=known)
        return False
