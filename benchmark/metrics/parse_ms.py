"""Mean ``serve.parse`` span per write parsed in the window (HTTP
handler: wire JSON to packed columns; native codec above 1 MB)."""


def read(ctx):
    s = ctx.spans.get("serve.parse")
    return s["total_ms"] / s["count"] if s else None
