"""Mean of the program's train span: burst dispatch, pacing fetch and mirror refresh. Host time, not train time."""


def read(run):
    spans = run.span_ms("Time/train_time")
    return sum(spans) / len(spans) if spans else None
