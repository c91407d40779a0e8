"""Replication-aware all-reduce: analytic byte model + measured HLO bytes on
an 8-device host mesh (subprocess).  The beyond-paper optimization of
DESIGN.md §2.4: replica axis carries ZERO steady-state gradient traffic."""

import os
import subprocess
import sys
import textwrap
import time

from repro.core import ReplicationPlan
from repro.distributed import allreduce_bytes

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core.replication import (ReplicationPlan, make_rdp_mesh,
        REPLICA_AXIS, BATCH_AXIS)
    from repro.roofline.hlo_cost import walk_hlo

    plan = ReplicationPlan(n_data=8, n_batches=4)
    mesh = make_rdp_mesh(plan, model_parallel=1)
    g = jnp.zeros((1024, 256), jnp.float32)
    spec = P((REPLICA_AXIS, BATCH_AXIS), None)

    def plain(x):
        return jax.lax.pmean(x, (REPLICA_AXIS, BATCH_AXIS))
    def rdp(x):
        return jax.lax.pmean(x, BATCH_AXIS)

    out = {}
    for name, fn in (("plain", plain), ("rdp", rdp)):
        f = jax.jit(shard_map(fn, mesh=mesh, in_specs=spec,
                              out_specs=spec))
        txt = f.lower(g).compile().as_text()
        w = walk_hlo(txt, pod_size=4)  # 'pod' = replica block of 4 batches
        out[name] = (w.coll_ici + w.coll_dci, w.coll_dci)
    print("RESULT", out["plain"][0], out["plain"][1], out["rdp"][0], out["rdp"][1])
    """
)


def run():
    plan = ReplicationPlan(n_data=32, n_batches=16)
    g_bytes = 500 * 2**20  # 0.5 GB of fp32 gradients
    model = {m: allreduce_bytes(g_bytes, plan, m) for m in ("plain", "rdp", "weighted")}
    desc = ";".join(
        f"{m}:total={v['total']/2**20:.0f}MB,cross={v['cross']/2**20:.0f}MB"
        for m, v in model.items()
    )
    rows = [("collective_bytes_model", 0.0, desc)]

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child lowers on 8 host devices; the chip stays with the parent
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=300,
    )
    dt = time.perf_counter() - t0
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")]
    assert line, r.stderr[-2000:]
    p_tot, p_dci, r_tot, r_dci = (float(x) for x in line[0].split()[1:])
    assert r_tot < p_tot  # replication discount measured in real HLO
    assert r_dci == 0.0  # no cross-replica traffic in steady state
    rows.append(
        (
            "collective_bytes_hlo_8dev",
            dt * 1e6,
            f"plain={p_tot/1e6:.2f}MB(cross={p_dci/1e6:.2f});"
            f"rdp={r_tot/1e6:.2f}MB(cross={r_dci/1e6:.2f})",
        )
    )
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
