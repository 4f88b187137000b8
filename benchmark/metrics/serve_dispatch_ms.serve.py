"""The server's own median request latency (queue wait + dispatch) over its
last 512 requests: `InferenceServer.snapshot()["latency_p50_s"]`."""


def read(run):
    v = run.counters.get("server_latency_p50_s")
    return None if v is None else 1e3 * v
