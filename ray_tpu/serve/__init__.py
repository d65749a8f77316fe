"""ray_tpu.serve — model/application serving over the actor runtime.

Reference capability: python/ray/serve (controller, proxy, replicas, pow-2
routing, dynamic batching, autoscaling) re-designed TPU-first: the flagship
deployment is a continuous-batched LLM decode engine (serve.llm) with a
paged KV cache resident in HBM and one compiled program per decode chunk.
"""

from ray_tpu.serve.api import (
    delete,
    get_app_handle,
    get_deployment_handle,
    http_address,
    http_addresses,
    run,
    shutdown,
    start,
    status,
)
from ray_tpu.serve.batching import batch
from ray_tpu.serve.deployment import Application, AutoscalingConfig, Deployment, deployment
from ray_tpu.serve.handle import DeploymentHandle, DeploymentResponse
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed
from ray_tpu.serve.rpc_ingress import ServeRpcClient
from ray_tpu.serve import schema

__all__ = [
    "ServeRpcClient",
    "get_multiplexed_model_id",
    "multiplexed",
    "schema",
    "Application",
    "AutoscalingConfig",
    "Deployment",
    "DeploymentHandle",
    "DeploymentResponse",
    "batch",
    "delete",
    "deployment",
    "get_app_handle",
    "get_deployment_handle",
    "http_address",
    "http_addresses",
    "run",
    "shutdown",
    "start",
    "status",
]
