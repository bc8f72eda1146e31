"""Host ms per wave of the hot-swap: the span around ``commit_store`` until
the tenant's new state is ready on the device, mean over the window."""


def read(ctx):
    spans = ctx.spans.named("commit_store")
    waves = ctx.traffic.layer["waves"]
    spans = spans[-waves:] if waves else []
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b, _ in spans) / len(spans)
