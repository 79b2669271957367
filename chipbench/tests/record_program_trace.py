"""Record the small trace that shows the program's own spans and scopes.

    python chipbench/tests/record_program_trace.py <out_dir>

Run on the chip. Inside a ``chipbench.window`` span it drives a tiny
``PlanRuntime`` through one training step and a tiny ``ServeEngine``
through three prefills (two prompt lengths warmed before the trace, one
new), two decode ticks and a release, so the trace holds every
``repro.runtime.*`` and ``repro.serve.*`` span and the step program's
named scopes. The newest ``*.xplane.pb`` is written, gzipped, to
``<out_dir>/program.xplane.pb.gz``.
"""

from __future__ import annotations

import gzip
from pathlib import Path
import sys
import tempfile

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.schedule import make_plan  # noqa: E402
from repro.models.common import ModelConfig  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.runtime import PlanRuntime  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402
from repro.serve.arrival import Request  # noqa: E402
from repro.serve.batching import ContinuousBatcher, RequestQueue  # noqa: E402

CFG = ModelConfig(
    name="trace-tiny", family="dense", num_layers=2, d_model=128, num_heads=2,
    num_kv_heads=2, d_ff=256, vocab_size=256, dtype=jnp.bfloat16, param_dtype=jnp.float32,
)


def serve_round(engine, lengths, rid0):
    """Admit one request per prompt length, prefill them, decode twice and
    release the first."""
    queue, batcher = RequestQueue(), ContinuousBatcher(engine.max_slots)
    for i, n in enumerate(lengths):
        queue.push(Request(rid0 + i, 0.0, n, 3))
    admitted = batcher.admit(queue, 0.0)
    engine.prefill(admitted)
    for inf in admitted:
        inf.tokens_emitted = 1
    for _ in range(2):
        engine.decode_tick(batcher.in_flight)
        for inf in batcher.in_flight:
            inf.tokens_emitted += 1
    engine.release([admitted[0].slot])
    jax.block_until_ready(engine.kv)


def main(out: str) -> int:
    rt = PlanRuntime(CFG, 1, make_optimizer("adamw"), global_batch=4, seq_len=128)
    rt.switch_to(make_plan(1, 4, 1).lower())
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG.vocab_size, (4, 128)).astype(np.int32)
    rt.run_iteration(tokens, tokens)  # compiled before the trace
    engine = ServeEngine(CFG, 2, max_slots=4, max_len=64)
    engine.switch_to(make_plan(2, 2, 2, micro_batch_size=2).lower())
    serve_round(engine, (16, 32), 0)

    d = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # Python calls would make the file megabytes
    options.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=options)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        rt.run_iteration(tokens, tokens)
        serve_round(engine, (16, 32, 24), 100)
    jax.profiler.stop_trace()
    rt.cache.shutdown()
    engine.runtime.cache.shutdown()
    src = sorted(Path(d).glob("**/*.xplane.pb"))[-1]
    Path(out).mkdir(parents=True, exist_ok=True)
    (Path(out) / "program.xplane.pb.gz").write_bytes(gzip.compress(src.read_bytes(), 9))
    print(src.stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
