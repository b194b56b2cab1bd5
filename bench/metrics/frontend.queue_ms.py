"""frontend.queue_ms: mean admission-to-dequeue wait of the window's
requests, as the frontend's own ``ServeStats.queue_ms`` records it."""


def read(run):
    return float(run.window.queue_ms)
