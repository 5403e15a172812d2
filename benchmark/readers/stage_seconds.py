"""Host seconds of one `profiler.record_stage` stage over the window, per
recorded event, times `scale`."""


def read(result, stage: str, scale: float = 1.0):
    s = result.stages.get(stage)
    if not s or not s["events"]:
        return None
    return s["seconds"] / s["events"] * scale
