"""The busiest expert's rows over the mean rows of an expert that got
rows, per MoE layer call of the window's decode steps: (busiest rows
summed over the calls / calls) / (rows routed / experts that got rows),
from the engine's routing counts (bench/moelog.py). 1 is an even spread
over the experts hit; a grouped expert call waits on its busiest expert."""
import moelog


def read(run):
    log = moelog.entries(run, phases=("decode",))
    calls = sum(e[3] for e in log)
    rows = sum(e[4] for e in log)
    hit = sum(e[5] for e in log)
    busiest = sum(e[6] for e in log)
    if not calls or not rows:
        return None
    return (busiest / calls) / (rows / hit)
