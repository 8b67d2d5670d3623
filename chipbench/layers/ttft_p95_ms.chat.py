"""95th percentile of the time to first token over every request due in
the window, from its due time; a request never served counts its whole
wait.  With a few tens of requests in a window it sits near their
largest, so it is recorded beside ``ttft_mean_ms`` and not judged."""


def read(ctx):
    return ctx["metrics"].get("ttft_p95_ms")
