"""Host time of one engine launch: the wall of a ``compile_cache.run``
span less the device's busy time inside it (state build and padding,
upload, readback), averaged over the traced launches (profiler trace)."""


def read(obs):
    t = obs.trace
    if t is None or not t.launch_span_s:
        return None
    host = [span - busy for span, busy in zip(t.launch_span_s,
                                                t.launch_busy_s)]
    return 1e3 * sum(host) / len(host)
