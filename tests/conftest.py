"""Shared test utilities.

NOTE: XLA_FLAGS / device-count forcing is NOT set here: it must precede
the first jax import, so multi-device tests spawn subprocesses via
``run_with_devices``.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int, timeout: int = 600):
    """Run ``code`` in a subprocess with n forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout
