"""Compile the planning path's kernels for a described TPU v5e chip.

Nothing runs.  The TPU compiler that ships with jaxlib compiles each kernel
at the shapes the planner dispatches, for a chip that is described rather
than attached, and refuses what the chip would refuse: blocks that break
the (8, 128) tiling, primitives Mosaic cannot lower, VMEM overflows.
Interpret-mode tests on the CPU cannot see any of that.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coded import kernel as CK
from repro.kernels.sojourn_sweep import kernel as SK

# fleet dispatch of the planning sweep: K=256 bootstrap resamples x the
# largest split (B=200 replica-sets) x 300 jobs, one policy group of 2
FLEET = dict(cells=256, jobs=300, groups=200, policies=2)
# benchmarks/bench_coding.py: N=16 workers, 6000 trials, MDS s in {4, 8, 12}
CODED = dict(cells=3, trials=6000, workers=16, block_dim=2048)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to a persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(one_chip, no_compile_cache):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _sweep_args(spec):
    c, j, g, p = (FLEET[k] for k in ("cells", "jobs", "groups", "policies"))
    return (spec((j,)), spec((c, j, g)), spec((c, j, g)), spec((p,), jnp.int32),
            spec((c, p)), spec((p, j), jnp.bool_), spec((c,), jnp.int32))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("resolve", [True, False])
def test_sojourn_pallas_compiles_at_fleet_shape(spec, resolve):
    compiled = SK.sojourn_cells_pallas.lower(
        *_sweep_args(spec), interpret=False, resolve=resolve).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("resolve", [True, False])
def test_sojourn_vmap_compiles_at_fleet_shape(spec, resolve):
    compiled = SK.sojourn_cells_vmap.lower(*_sweep_args(spec),
                                           resolve=resolve).compile()
    c, j, p = FLEET["cells"], FLEET["jobs"], FLEET["policies"]
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes >= 4 * (c * p * j + c * p)


def _coded_args(spec):
    shape = (CODED["cells"], CODED["trials"], CODED["workers"])
    return spec(shape), spec((CODED["cells"],), jnp.int32)


def test_coded_pallas_compiles_at_bench_shape(spec):
    compiled = SK.coded_cells_pallas.lower(*_coded_args(spec),
                                           interpret=False).compile()
    _assert_kernel(compiled)


def test_coded_vmap_compiles_at_bench_shape(spec):
    compiled = SK.coded_cells_vmap.lower(*_coded_args(spec)).compile()
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes >= 4 * CODED["cells"] * CODED["trials"]


# (rows, k): MDS encode (N x k) and decode (k x k) at s=4, and the cyclic
# gradient code's one-row decode
COMBINE_SHAPES = [(16, 12), (12, 12), (1, 14)]


@pytest.mark.parametrize("rows,k", COMBINE_SHAPES)
def test_combine_pallas_compiles_at_bench_shape(spec, rows, k):
    compiled = CK.combine_pallas.lower(
        spec((rows, k)), spec((k, CODED["block_dim"])),
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("rows,k", COMBINE_SHAPES)
def test_combine_jit_compiles_at_bench_shape(spec, rows, k):
    compiled = CK.combine_jit.lower(
        spec((rows, k)), spec((k, CODED["block_dim"]))).compile()
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes >= 4 * rows * CODED["block_dim"]


@pytest.fixture(scope="module")
def serving(spec):
    """A reduced serving engine's prefill and decode programs, compiled for
    the described chip at batch 8."""
    from repro.serving import ReplicatedServingEngine, ServeEngineConfig

    engine = ReplicatedServingEngine(ServeEngineConfig(
        batch_size=8, prompt_len=16, gen_tokens=4, max_len=32))
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = shapes(engine.params)
    prompts = {"tokens": spec((8, 16), jnp.int32)}
    tok, state = jax.eval_shape(engine._prefill, params, prompts)
    return {
        "prefill": engine._prefill.lower(params, prompts).compile(),
        "decode": engine._decode.lower(params, shapes(state), shapes(tok),
                                       spec((), jnp.int32)).compile(),
    }


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_serving_pick_reads_materialised_logits(serving, program):
    """The greedy pick inside the serving programs reduces the bfloat16
    logits as a buffer of their own, as the eager pick did: no fused
    computation holds both the unembedding's matmul and the argmax."""
    blocks = serving[program].as_text().split("\n\n")
    assert any("iota(" in b and "reduce(" in b for b in blocks)
    assert not [b.splitlines()[0] for b in blocks
                if "convolution(" in b and "iota(" in b and "reduce(" in b]


# the chat cell's decode: full-width qwen2-0.5b, batch 8, a 1,152-slot cache
CHAT = dict(batch=8, max_len=1152)


def _copies(block, elements):
    """Names of the copies in one HLO computation whose (first) output
    buffer holds ``elements`` elements."""
    out = []
    for line in block.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.+?) (copy|copy-start)\(%", line)
        if m:
            dims = re.search(r"\[([\d,]*)\]", m.group(2)).group(1)
            if math.prod(int(d) for d in dims.split(",") if d) == elements:
                out.append(m.group(1))
    return out


def test_dense_decode_updates_the_cache_in_place(spec):
    """The decode step keeps the stacked KV caches in the layer scan's
    carry: the layer loop copies no layer's cache (restacking them as scan
    outputs took three copies each of K and V per layer), and the entry
    makes at most one pass over each whole cache."""
    from repro.configs import get_config
    from repro.models import Shard, decode_state_shapes, decode_step, init_params

    cfg = get_config("qwen2-0.5b")
    b, s = CHAT["batch"], CHAT["max_len"]
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = shapes(decode_state_shapes(cfg, b, s))
    shard = Shard.local()
    text = jax.jit(
        lambda p, st, t, c: decode_step(cfg, shard, p, st, t, c)
    ).lower(params, state, spec((b, 1), jnp.int32),
            spec((), jnp.int32)).compile().as_text()

    layer = b * s * cfg.n_kv_heads * cfg.head_dim
    blocks = text.split("\n\n")
    entry = [blk for blk in blocks if blk.lstrip().startswith("ENTRY")]
    assert len(entry) == 1
    assert not [c for blk in blocks if blk not in entry
                for c in _copies(blk, layer)]
    assert len(_copies(entry[0], cfg.n_layers * layer)) <= 2


def _ops_with(text, elements):
    """(computation, instruction) of every copy, fusion or dynamic slice in
    the program whose (first) output holds ``elements`` elements."""
    out = []
    for block in text.split("\n\n"):
        head = block.strip().split(" ", 1)[0] if block.strip() else ""
        for line in block.splitlines():
            m = re.match(r"\s*(?:ROOT )?%(\S+) = \S+?\[([\d,]*)\]\S* "
                         r"(copy|copy-start|fusion|dynamic-slice)\(", line)
            if m and math.prod(
                    int(d) for d in m.group(2).split(",") if d) == elements:
                out.append((head, m.group(1)))
    return out


def test_mla_decode_reads_the_held_experts_in_place(spec, monkeypatch):
    """deepseek-v2-lite-ep8's decode step at the chat cell's shape: Mosaic
    takes the held-expert kernel at its block sizes, three calls in the
    layer loop, and no op copies or slices out a layer's expert stack (8 x
    2048 x 1408, which a scanned stack fed to ``ragged_dot`` was) or the
    whole stack."""
    from repro.configs import get_config
    from repro.kernels.held_experts import kernel as HK
    from repro.models import Shard, decode_state_shapes, decode_step, init_params

    # the kernel asks the default backend, which is the CPU here
    monkeypatch.setattr(HK, "resolve_interpret", lambda interpret=None: False)
    cfg = get_config("deepseek-v2-lite-ep8")
    b, s = CHAT["batch"], CHAT["max_len"]
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = shapes(decode_state_shapes(cfg, b, s))
    assert state["kv"].shape == (27, 8, 1152, 576)
    text = jax.jit(
        lambda p, st, t, c: decode_step(cfg, Shard.local(), p, st, t, c)
    ).lower(params, state, spec((b, 1), jnp.int32),
            spec((), jnp.int32)).compile().as_text()

    calls = re.findall(r"%held_experts_gmm\S* = .*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 3
    layer = cfg.moe.held * cfg.d_model * cfg.moe.d_expert
    assert not _ops_with(text, layer)
    assert not _ops_with(text, (cfg.n_layers - 1) * layer)
