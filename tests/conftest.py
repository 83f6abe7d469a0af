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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def force_strategy(monkeypatch):
    """``force_strategy(name)`` runs every segment reduction on the kernel
    ``name`` wherever it is eligible. Where it is not, a packed group-by
    gets no kernel (the generic path runs) and a count reduction keeps the
    shape's own rule."""
    from fugue_tpu.jax_backend import groupby

    rule = groupby.choose_strategy

    def force(name):
        def pick(
            platform, num_segments, float_dtype=np.float32,
            need_int=False, count_only=False,
        ):
            if name in groupby.eligible_strategies(
                num_segments, need_int, count_only
            ):
                return name
            if count_only:
                return rule(
                    platform, num_segments, float_dtype, need_int, count_only
                )
            return None

        monkeypatch.setattr(groupby, "choose_strategy", pick)

    return force
