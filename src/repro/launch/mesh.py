"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and tests/benches must keep seeing 1 device.

``ensure_host_devices`` is the CLI affordance for mesh-spanning serving on
the CPU: XLA's forced host device count must be set before jax first
initializes, which is too late once a launcher module has imported jax — so
on the CPU platform the launcher re-execs itself once with the flag set. On
an accelerator the process holds its chips, so too few is an error.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int],
              axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD places what the
    specs leave open)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips/pod (v5e pod); 2 pods over DCN when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))


def ensure_host_devices(n: int) -> None:
    """Guarantee jax sees >= ``n`` devices. No-op when enough exist. On the
    CPU platform, re-exec this process ONCE with
    ``--xla_force_host_platform_device_count`` (the flag only takes effect
    before jax initializes; a CPU parent holds no chip a child would need).
    On any other platform, raise: the process holds its chips, and a
    relaunched child could not get them."""
    devices = jax.devices()
    if len(devices) >= n:
        return
    platform = devices[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"need {n} devices but jax sees {len(devices)} {platform} "
            f"device(s): run on a host with {n} chips or shrink the mesh")
    if os.environ.get("_REPRO_MESH_RELAUNCHED"):
        raise RuntimeError(
            f"need {n} devices but jax sees {len(devices)} even after "
            f"forcing the host platform — shrink the mesh")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["_REPRO_MESH_RELAUNCHED"] = "1"
    print(f"[mesh] {len(devices)} cpu device(s) < {n}: relaunching with "
          f"{n} forced host devices")
    sys.stdout.flush()
    raise SystemExit(subprocess.run(
        [sys.executable, sys.argv[0]] + sys.argv[1:], env=env).returncode)
