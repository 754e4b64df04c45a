"""Layer: device. The share of the traced slice's busy time under NO
named scope: busy less the merged time of every op that holds an `mx_*`
scope, each op joined in the map of the program whose module run it lies
in (`lib/program_share.py`). The run's log gets the exclusive table (a
row a program and set of leaf scopes, rows summing to busy), the kinds
of instruction without a scope that took most time with where each came
from (a training step's `copy`: which transposes and reshapes), and a
line of counts for each inspected executable: what a change
to names and metadata alone leaves as it was."""
from ..lib import program_share


def reduce(events, spans, counters, cell):
    def say(line):
        print(f"[bench {cell.get('workload')}] {line}", flush=True)

    for exe, info in sorted(program_share.inspections().items()):
        say(f"inspected {exe} ({info.get('module')}): "
            f"{program_share.digest(info)}")
    for exe, why in sorted(program_share.not_inspected().items()):
        say(f"NOT inspected: {exe} ({why}): its ops count under no scope")
    shares = program_share.reduce(events, *cell["window"])
    if shares is None:
        return None
    for line in shares.table():
        say(line)
    return shares.unscoped_pct()
