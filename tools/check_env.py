#!/usr/bin/env python
"""Environment probe: which JAX is installed and which devices it sees.

    PYTHONPATH=src python tools/check_env.py

Exit status is 0 when JAX and the repro package imported cleanly, 1
otherwise — handy as a preflight before the real test run.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def main() -> int:
    try:
        import jax
    except Exception as e:  # pragma: no cover - catastrophic env
        print(f"FATAL: jax failed to import: {e}")
        return 1
    try:
        import repro.runtime  # noqa: F401
    except Exception as e:
        print(f"jax {jax.__version__} imported, but repro did not: {e}")
        return 1
    devices = jax.devices()
    print(f"jax version:  {jax.__version__}")
    print(f"platform:     {devices[0].platform}")
    print(f"device_kind:  {devices[0].device_kind}")
    print(f"device count: {len(devices)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
