"""Engine (ResilientEngine, ExecutionCore): mean host time, in us, inside
run_plan(plan, sync=False): pad, transfer to the device, dispatch of
the compiled step and the ladder's bookkeeping."""

from chipbench import measures


def read(run):
    return measures.dispatch_us_per_plan(run)
