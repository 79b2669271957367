"""Record the small device trace the trace-reduction tests read.

    python chipbench/tests/record_trace.py <out_dir>

Run on the chip: three annotated "steps" of a small jitted program with a
host pause between them, traced inside a ``chipbench.window`` span. The
newest ``*.xplane.pb`` is copied to ``<out_dir>/small.xplane.pb``.
"""

from __future__ import annotations

from pathlib import Path
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.train.data"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("chipbench.train.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(Path(d).glob("**/*.xplane.pb"))[-1]
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(src, Path(out) / "small.xplane.pb")
    print(src.stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
