"""Host milliseconds ``ddp.train_step`` spends per step of the window
(pre-dispatch, lock wait, dispatch, post-dispatch), from the engine's own
counters, reset at the window's start."""


def read(context):
    snapshot = context["counters"]["host_overhead"]
    return sum(snapshot[f"{phase}_ms_per_step"]
               for phase in ("pre", "lock_wait", "dispatch", "post"))
