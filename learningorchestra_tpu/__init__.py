"""learningOrchestra-TPU — a TPU-native ML pipeline orchestration framework.

A ground-up rebuild of the capabilities of learningOrchestra
(reference: /root/reference, REST-orchestrated ML pipelines over Docker
Swarm + Flask + MongoDB + Spark) on an idiomatic JAX/XLA/pjit/Pallas
stack:

- One REST control plane with the reference's URI contract
  (``/api/learningOrchestra/v1/{service}/{tool}``, async 201 +
  ``finished``-flag polling; reference krakend.json:1-1773).
- A catalog (SQLite metadata + Parquet datasets + typed binary
  artifacts) replacing MongoDB-as-everything (reference
  docker-compose.yml:42-90).
- A JAX runtime: device-mesh manager, jit/pjit training engines,
  double-buffered host->HBM input feed, verified step checkpointing.
- A parallelism library: DP/FSDP/TP/PP/SP(ring attention)/Ulysses/EP
  over `jax.sharding.Mesh` — all absent in the reference (SURVEY §2.4).
"""

__version__ = "0.1.0"

from learningorchestra_tpu.config import Config, get_config  # noqa: F401
