"""JAX runtime: the TPU-native replacement for the reference's compute
substrate (in-process TF/sklearn ``fit`` calls, binary_execution.py:
177-189, and the Spark cluster, SURVEY §L4).

- ``mesh``       — device-mesh manager and axis conventions
- ``data``       — host->device double-buffered input feed
- ``engine``     — jit/pjit train/eval/predict loops
- ``checkpoint`` — verified msgpack step checkpointing + pytree artifact IO
- ``distributed``— multi-host initialization (jax.distributed)
"""
