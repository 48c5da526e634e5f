"""Device time of every program other than the job's step (the live
drain's chunk prefix and the report's histogram kernels, with the copies
and slices around them) per run of the step program in the trace."""

STEP_MODULE = "jit_train_step"


def read(run):
    steps = run.trace.module_calls.get(STEP_MODULE, 0)
    seconds = sum(s for m, s in run.trace.module_s.items()
                  if m != STEP_MODULE)
    if steps == 0 or seconds <= 0:
        return None
    return 1e3 * seconds / steps
