"""Four host devices for the harness's tests, so a tensor-parallel pod can
be deployed on the CPU; set before JAX initialises, and appended so an
explicit topology in ``XLA_FLAGS`` wins."""

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                 ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
