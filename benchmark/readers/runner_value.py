"""A number the runner took with the benchmark's own clock or counters:
`key` of its end-to-end values, else of its notes, times `scale`."""


def read(result, key: str, scale: float = 1.0):
    value = result.values.get(key, result.notes.get(key))
    return None if value is None else value * scale
