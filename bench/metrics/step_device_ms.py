"""Diffusion programs: mean device time of one run of the tick ``step``
executables that started in the profiled window, in ms.  (A tick can outlast
the profiled stretch, so runs are counted, not ticks.)"""


def read(run):
    p = run.profile
    if p is None:
        return None
    seconds, runs = p.module_time("jit_step")
    return 1e3 * seconds / runs if runs else None
