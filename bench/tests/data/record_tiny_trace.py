"""Record the small v5e trace the reducer's test reads (run on a TPU):

    python3 bench/tests/data/record_tiny_trace.py [output directory]

A ``bench.window`` span around a few calls of the rbf Pallas kernel and a
jitted matmul, with a ``bench.submit`` span that sleeps between them (an
idle gap the host span names). Writes ``tiny_v5e.xplane.pb`` and
``tiny_v5e.expect.json`` (the reduction's numbers, which the test then
holds the reducer to) into the directory given, by default beside this
file.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]


def main() -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from bench import traces
    from repro.kernels.rbf.ops import rbf_covariance

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    X = jax.random.normal(jax.random.PRNGKey(0), (512, 21), jnp.float32)
    mm = jax.jit(lambda a: jnp.dot(a, a.T, precision="highest"))
    jax.block_until_ready((rbf_covariance(X, X, 1.0, impl="pallas"), mm(X)))
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir, profiler_options=traces.options())
    with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
        for _ in range(3):
            jax.block_until_ready(rbf_covariance(X, X, 1.0, impl="pallas"))
            with jax.profiler.TraceAnnotation("bench.submit"):
                time.sleep(0.01)
            jax.block_until_ready(mm(X))
    jax.profiler.stop_trace()
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE
    out.mkdir(parents=True, exist_ok=True)
    target = out / "tiny_v5e.xplane.pb"
    shutil.copy(traces.find_xplane(log_dir), target)
    shutil.rmtree(log_dir)
    tr = traces.load(str(target))
    expect = {"window_s": tr.window_s, "busy_s": tr.busy_s,
              "rbf_s": tr.time_s(traces.RBF_KERNEL),
              "gap_names": sorted({n for n, _ in tr.idle_gaps()}),
              "device": jax.devices()[0].device_kind}
    (out / "tiny_v5e.expect.json").write_text(json.dumps(expect, indent=1))
    print(json.dumps(expect), target.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
