"""Subprocess entry point for the multi-host serving cases.

Pins the CPU platform and the host-device count (8) before any jax
backend use, then delegates to repro.testing.serve_cases.main.
Never import this from pytest.
"""
import sys

from repro.tuning.backend import apply_backend_setup

apply_backend_setup("cpu", host_device_count=8)

from repro.testing.serve_cases import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
