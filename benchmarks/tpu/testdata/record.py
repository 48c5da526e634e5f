"""Record ``window.xplane.pb``, the small TPU trace the trace-reduction
test reads.

    python3 benchmarks/tpu/testdata/record.py      # on a TPU host

Inside one ``window`` span it runs, each in a span of its own: a whole-log
``pallas`` replay of a 0.05 s capture (the fused pipeline with the ``fold``
kernel), a chunked replay (``carry_cumsum`` per chunk), a 50 ms host sleep
in ``train/loader_wait`` (a known idle gap) and a ``tag_hist`` call.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

SLEEP_S = 0.05


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from testdata import capture
    harness.require_tpu(1)
    from repro.core.detector import detect_offline
    from repro.core.session import ProfileSession
    from repro.kernels import ops

    cap = capture.generate(capture.SMALL, 1)
    log, tags, stacks = capture.to_program(cap)
    keys = jnp.asarray(np.arange(8192) % 512, jnp.int32)

    def work():
        with harness.span("replay"):
            detect_offline(log, tags, stacks, cap.n_min, backend="pallas")
        with harness.span("replay/chunked"):
            ProfileSession.offline(log, tags, stacks, n_min=cap.n_min,
                                   backend="pallas",
                                   chunk_events=16384).result()
        with harness.span("train/loader_wait"):
            time.sleep(SLEEP_S)
        with harness.span("detect"):
            jax.block_until_ready(ops.tag_histogram(keys, num_bins=512))

    work()                                      # compile outside the trace
    out = tempfile.mkdtemp(prefix="bench_record_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        with harness.span("window"):
            work()
        jax.profiler.stop_trace()
        src = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
        # source locations in the HLO metadata name the checkout: blank
        # them to the same length, so the protobuf stays valid
        root = (str(HERE.parents[2]) + "/").encode()
        data = Path(src).read_bytes().replace(root, b"_" * len(root))
        (HERE / "window.xplane.pb").write_bytes(data)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    print(f"wrote {HERE / 'window.xplane.pb'} "
          f"({(HERE / 'window.xplane.pb').stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
