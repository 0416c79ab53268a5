"""Host time per dispatch in the window: the service's staging and result
slicing plus the pipeline's enqueue and rule-table feedback (both programs'
own host spans), over the service's dispatches."""


def read(run):
    n = run["service"]["dispatches"]
    if not n:
        return None
    return 1e3 * (run["service"]["host_s"] + run["pipeline"]["host_s"]) / n
