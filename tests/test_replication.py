"""RDP plan, host aggregation semantics, and multi-device shard_map paths
(the latter in a subprocess with forced host devices)."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import (
    ReplicationPlan,
    aggregate_host,
    batch_index_for_data_coord,
)


def test_plan_validation():
    with pytest.raises(ValueError):
        ReplicationPlan(n_data=8, n_batches=3)
    p = ReplicationPlan(n_data=8, n_batches=4)
    assert p.replication == 2
    assert not p.is_full_diversity and not p.is_full_parallelism
    assert ReplicationPlan(8, 1).is_full_diversity
    assert ReplicationPlan(8, 8).is_full_parallelism


def test_batch_index_map():
    p = ReplicationPlan(n_data=8, n_batches=4)
    assert [batch_index_for_data_coord(p, w) for w in range(8)] == [
        0, 1, 2, 3, 0, 1, 2, 3,
    ]


def test_expected_step_stats_match_order_stats():
    from repro.core import ShiftedExponential, completion_mean, completion_var

    p = ReplicationPlan(n_data=16, n_batches=4)
    d = ShiftedExponential(delta=0.5, mu=2.0)
    m, v = p.expected_step_stats(d)
    assert m == completion_mean(d, 16, 4)
    assert v == completion_var(d, 16, 4)


def test_host_aggregation_unbiased_mean():
    plan = ReplicationPlan(n_data=8, n_batches=4)
    grads = [
        {"w": np.full(3, float(batch_index_for_data_coord(plan, w)))}
        for w in range(8)
    ]
    alive = np.ones(8, bool)
    agg, nb = aggregate_host(grads, alive, plan)
    np.testing.assert_allclose(agg["w"], 1.5)
    assert nb == 4
    # kill one replica of batch 0: still unbiased
    alive2 = alive.copy(); alive2[0] = False
    agg2, nb2 = aggregate_host(grads, alive2, plan)
    np.testing.assert_allclose(agg2["w"], 1.5)
    assert nb2 == 4
    # kill BOTH replicas of batch 2 (coords 2 and 6): renormalizes
    alive3 = alive.copy(); alive3[2] = alive3[6] = False
    agg3, nb3 = aggregate_host(grads, alive3, plan)
    np.testing.assert_allclose(agg3["w"], (0 + 1 + 3) / 3)
    assert nb3 == 3


def test_host_aggregation_all_dead_raises():
    plan = ReplicationPlan(n_data=4, n_batches=2)
    grads = [{"w": np.ones(2)}] * 4
    with pytest.raises(RuntimeError):
        aggregate_host([None] * 4, np.zeros(4, bool), plan)


_SUBPROCESS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.replication import (ReplicationPlan, make_rdp_mesh,
        aggregate_gradients, REPLICA_AXIS, BATCH_AXIS)
    from repro.distributed.collectives import (hierarchical_allreduce,
        replication_aware_pmean)

    plan = ReplicationPlan(n_data=8, n_batches=4)
    mesh = make_rdp_mesh(plan, model_parallel=1)
    spec = P((REPLICA_AXIS, BATCH_AXIS))
    g = jnp.arange(8, dtype=jnp.float32) % 4
    alive = jnp.ones(8, jnp.float32).at[2].set(0.).at[6].set(0.)

    def w(gl, al):
        out, nb = aggregate_gradients({"w": gl}, al, mode="weighted")
        return out["w"], nb
    f = jax.jit(shard_map(w, mesh=mesh, in_specs=(spec, spec),
                          out_specs=(spec, spec)))
    out, nb = f(g, alive)
    np.testing.assert_allclose(np.asarray(out), (0+1+3)/3, rtol=1e-6)
    assert float(nb[0]) == 3.0

    # hierarchical == pmean over batch (steady state)
    def h(gl):
        return hierarchical_allreduce({"w": gl.reshape(1, -1) * jnp.ones((3, 1))})["w"]
    def pm(gl):
        return replication_aware_pmean({"w": gl.reshape(1, -1) * jnp.ones((3, 1))})["w"]
    fh = jax.jit(shard_map(h, mesh=mesh, in_specs=spec, out_specs=P(None, (REPLICA_AXIS, BATCH_AXIS))))
    fp = jax.jit(shard_map(pm, mesh=mesh, in_specs=spec, out_specs=P(None, (REPLICA_AXIS, BATCH_AXIS))))
    np.testing.assert_allclose(np.asarray(fh(g)), np.asarray(fp(g)), rtol=1e-6)

    # steady-state hierarchical path: NO collective crosses the replica axis
    txt = fp.lower(g).compile().as_text()
    import re
    for m in re.finditer(r"replica_groups=\\{\\{([^}]*)\\}", txt):
        ids = [int(x) for x in m.group(1).split(",")]
        # replica axis stride is 4 (outermost): groups must stay within one replica block
        assert max(ids) - min(ids) < 4, f"collective crosses replica axis: {ids}"
    print("SUBPROCESS_OK")
    """
)


def test_rdp_shard_map_aggregation_multi_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SUBPROCESS_OK" in r.stdout
