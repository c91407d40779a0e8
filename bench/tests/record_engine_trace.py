"""Record ``data/tiny_engine_v5e.xplane.pb`` on a TPU chip.

    python3 bench/tests/record_engine_trace.py <out.xplane.pb>

A reduced ``ReplicatedServingEngine`` (4 layers, width 128, batch 2,
prompt 16, 8 greedy tokens) serves ``serve(6)``, three jobs, under the
profiler with the Python tracer off, inside a ``bench.window`` span and a
``bench.serve`` span, with ``bench.prefill`` / ``bench.decode`` spans
around its jitted calls as the chat cell opens them.  Its shapes
are compiled first, outside the trace.

What is kept of the trace, to keep the file small: the planes
``/device:TPU:0`` (lines ``XLA Modules`` and ``XLA Ops``) and
``/host:CPU`` (the ``bench.`` and ``repro.`` spans), without event stats.
"""

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, os.path.dirname(HERE))

GEN_TOKENS = 8
N_REQUESTS = 6
KEEP = {"/device:TPU:0": ("XLA Modules", "XLA Ops"), "/host:CPU": None}


def strip(src: str, dst: str) -> None:
    """Copy the ``.xplane.pb`` at ``src`` to ``dst`` with only what the
    trace reduction reads (see the module docstring)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if plane.name not in KEEP:
            continue
        lines, used = KEEP[plane.name], set()
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if lines is not None and line.name not in lines:
                continue
            keep = [ev for ev in line.events if lines is not None
                    or plane.event_metadata[ev.metadata_id].name.startswith(
                        ("bench.", "repro."))]
            if not keep:
                continue
            nl = new.lines.add(id=line.id, display_id=line.display_id,
                               name=line.name, timestamp_ns=line.timestamp_ns,
                               duration_ps=line.duration_ps)
            for ev in keep:
                used.add(ev.metadata_id)
                nl.events.add(metadata_id=ev.metadata_id,
                              offset_ps=ev.offset_ps,
                              duration_ps=ev.duration_ps)
        for mid in used:
            md = plane.event_metadata[mid]
            new.event_metadata[mid].id = md.id
            new.event_metadata[mid].name = md.name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


def main(out: str) -> int:
    import jax

    from harness import tracing
    from repro.serving import ReplicatedServingEngine, ServeEngineConfig

    if jax.devices()[0].platform != "tpu":
        print("no TPU visible; nothing recorded", file=sys.stderr)
        return 2
    engine = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=4, n_batches=2, batch_size=2, prompt_len=16,
        gen_tokens=GEN_TOKENS, max_len=32, utilization=0.5, seed=11))
    for name in ("prefill", "decode"):
        real = getattr(engine, f"_{name}")

        def spanned(*args, _real=real, _label=f"bench.{name}"):
            with jax.profiler.TraceAnnotation(_label):
                return _real(*args)

        setattr(engine, f"_{name}", spanned)
    engine.serve(2)  # compile every shape outside the trace
    log_dir = tempfile.mkdtemp()
    try:
        with tracing.capture(log_dir):
            with jax.profiler.TraceAnnotation("bench.window"):
                with jax.profiler.TraceAnnotation("bench.serve"):
                    stats = engine.serve(N_REQUESTS)
        path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        strip(path, out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    if not all(s.tokens.shape == (GEN_TOKENS,) for s in stats):
        raise RuntimeError("a request came back without its tokens")
    print(f"{out}: {os.path.getsize(out)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
