"""The MoE routing counts of a run's window, as the program logs them.

`repro.serve.engine.MOE_LOG` holds one entry per prefill call and per
decode block of every engine in the process, (engine serial, the step's
`now`, phase, MoE layer calls, rows routed, experts that got rows, the
busiest expert's rows summed over the calls), and outlives the engine
that the serving driver frees before the readers run. The window's
entries are the newest engine's whose step started in the window:
0 < now < seconds on the window's clock (warm-up and set-up steps run at
now = 0, a pre-roll's before it, a drain's after). A program without the
log gives none.
"""


def entries(run, phases=("prefill", "decode")) -> list[tuple]:
    try:
        from repro.serve.engine import MOE_LOG
    except ImportError:
        return []
    if run.data.get("tracker") is None or not MOE_LOG:
        return []
    log = list(MOE_LOG)
    serial, seconds = log[-1][0], run.data["seconds"]
    return [e for e in log
            if e[0] == serial and 0.0 < e[1] < seconds and e[2] in phases]
