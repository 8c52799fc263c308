"""Root conftest: force a virtual 8-device CPU platform for all tests.

The chip runs chip_smoke.py and benchmarks/run.py; tests exercise the
multi-device sharding paths on the host (xla_force_host_platform_device_count), per the
driver contract. Both variables must be in place before jax initialises a backend,
which conftest import time guarantees; the jax.config update covers a jax that some
plugin imported earlier.
"""

import os

_platform = os.environ.get("SURGE_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)


def pytest_configure(config):
    # the tier-1 budget rests on `-m 'not slow'`: register the marker so a
    # typo'd @pytest.mark.sloow fails the -W error audit instead of silently
    # joining tier-1 (chaos soaks and minutes-long benches must stay out)
    config.addinivalue_line(
        "markers", "slow: minutes-long soak/bench tests excluded from the "
                   "tier-1 `-m 'not slow'` run")
    # build the csrc/ native libraries once per session when a compiler is
    # present (incremental — ~free when up to date), so tier-1 exercises the
    # native hot path instead of always taking the Python fallback. Without
    # a compiler the libraries stay absent and native-only tests skip with
    # a reason (see tests/test_native_gate.py / test_abi_drift.py).
    import shutil
    import subprocess

    if (shutil.which("g++")
            and os.environ.get("SURGE_SKIP_NATIVE_BUILD", "0") != "1"):
        build = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "csrc", "build.sh")
        try:
            proc = subprocess.run(["sh", build], capture_output=True,
                                  timeout=120)
            if proc.returncode != 0:
                print(f"csrc/build.sh failed (native tests will skip): "
                      f"{proc.stderr.decode(errors='replace')[-500:]}")
        except Exception as exc:  # noqa: BLE001 — the build is best-effort
            print(f"csrc/build.sh unavailable: {exc!r}")


def free_ports(n: int = 1) -> list:
    """Distinct ephemeral ports: all sockets stay bound until every port is
    chosen, so two consecutive calls cannot hand back the same port."""
    import socket

    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()
