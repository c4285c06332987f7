"""Shared pytest fixtures for the repro test suite.

The 8-device subprocess harness lives here: several suites
(test_dist, test_hserve, test_client, test_obs, test_multihost) verify
sharded serving on a forced (2, 4) CPU mesh, and XLA fixes its device
count at import time — once `jax` is imported in the pytest process,
no in-process test can change it. Each such test therefore runs its
body in a FRESH interpreter with
``--xla_force_host_platform_device_count=8`` set before the first jax
import, and reports results as one JSON line on stdout.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# imported before the test body, AFTER forcing the device count; the
# union of what every migrated suite's preamble used to import
_PREAMBLE = """
    import os
    os.environ["XLA_FLAGS"] = \
        "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.core
    from repro.launch.mesh import make_mesh
"""


def run_in_8dev_subprocess(body: str, timeout: int = 900) -> dict:
    """Run `body` in a fresh python with 8 forced XLA host devices.

    The body must end by printing ONE json document (its last stdout
    line is parsed and returned). Raises via assert on a non-zero exit,
    with the subprocess stderr tail in the message.
    """
    code = textwrap.dedent(_PREAMBLE) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(name="run_in_8dev_subprocess")
def run_in_8dev_subprocess_fixture():
    """The harness as a fixture, so tests take it as an argument
    instead of importing from conftest (which shadows easily)."""
    return run_in_8dev_subprocess
