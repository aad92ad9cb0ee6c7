"""Test configuration.

JAX runs on a virtual 8-device CPU mesh so multi-chip sharding paths
are exercised without TPU hardware (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip). Both settings
must land before any backend initializes. The tier-1 command also sets
JAX_PLATFORMS=cpu, which spawned agents inherit; the config update
here makes a bare `pytest` behave the same on a host with a chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
