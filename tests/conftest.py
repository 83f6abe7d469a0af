import os

# Force JAX onto a virtual 8-device CPU mesh for all tests: multi-chip
# sharding is validated without TPU hardware (the driver separately dry-runs
# the multichip path; see __graft_entry__.py).
#
# jax may already be imported when this runs, so the platform is set with
# config.update (before the first backend initialization) and not only
# through env vars.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
