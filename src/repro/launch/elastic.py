"""Elastic restart demo: survive a node-count change mid-solve.

Checkpoints are mesh-agnostic (utils/checkpoint.py saves unsharded), so a
job that loses devices restarts on a smaller mesh and continues from the
same iterate — the recovery path a 1000-node deployment needs.  This driver
simulates it in-process by re-sharding the restored state onto a new mesh.

    python -m repro.launch.elastic   # (uses XLA_FLAGS to fake 8 devices)
"""

import os

# fake CPU devices only: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import argparse
import shutil
import tempfile

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--gamma", type=float, default=0.995)
    args = ap.parse_args(argv)

    jax.config.update("jax_enable_x64", True)
    from repro.core import IPIOptions, generators
    from repro.core.driver import solve

    mdp = generators.garnet(args.n, 12, 6, gamma=args.gamma, seed=5)
    opts = IPIOptions(method="ipi_gmres", atol=1e-9, dtype="float64")
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_")
    try:
        from repro.launch.mesh import mesh_kwargs
        mesh8 = jax.make_mesh((8, 1), ("data", "model"), **mesh_kwargs(2))
        short = IPIOptions(method="ipi_gmres", atol=1e-9, dtype="float64",
                           max_outer=3)
        r1 = solve(mdp, short, mesh=mesh8, checkpoint_dir=ckpt_dir, chunk=1)
        print(f"[elastic] phase 1 on 8 devices: k={r1.outer_iterations} "
              f"res={r1.residual:.3e} (simulated failure)")

        # "lose" half the fleet: resume on a 4-device mesh
        mesh4 = jax.make_mesh(
            (4, 1), ("data", "model"),
            **mesh_kwargs(2, devices=np.array(jax.devices()[:4])))
        r2 = solve(mdp, opts, mesh=mesh4, checkpoint_dir=ckpt_dir, chunk=16)
        print(f"[elastic] phase 2 on 4 devices: {r2.summary()}")

        r_ref = solve(mdp, opts)
        dv = np.abs(r2.v - r_ref.v).max()
        print(f"[elastic] |v - v_ref|_inf = {dv:.2e}")
        assert r2.converged and dv < 1e-9
        print("[elastic] OK: elastic restart preserved the solve exactly")
        return 0
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
