"""Device ops of the reduced trace whose names match, shared by the trace
readers: their seconds and their count in the window."""


def matching(trace, patterns):
    names = [n for n in trace["op_seconds"]
             if any(p in n.lower() for p in patterns)]
    return (sum(trace["op_seconds"][n] for n in names),
            sum(trace["op_counts"][n] for n in names))
