"""Model FLOP utilisation of the job's step on the device: forward and
backward model FLOPs of a step (no recomputation), times the runs of the
step program in the trace, over the device time of those runs, over the
chip's bf16 peak."""
import flops_bytes

STEP_MODULE = "jit_train_step"


def read(run):
    seconds = run.trace.module_s.get(STEP_MODULE, 0.0)
    steps = run.trace.module_calls.get(STEP_MODULE, 0)
    if steps == 0 or seconds <= 0:
        return None
    job = run.cell.config["train"]
    flops = flops_bytes.train_step_flops(run.cell.config, job["batch"],
                                         job["text_len"])
    return 100.0 * flops * steps / seconds / run.peak["bf16_flops_per_s"]
