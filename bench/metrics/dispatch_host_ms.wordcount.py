"""Host time per chunk of the word count's stream: the dispatcher's
``dispatch.stage`` (cutting the chunk) and ``dispatch.launch`` (finding its
executable and making the async call) spans, over every chunk of the
window, from each request's ``DispatchReport.stats``."""
LAYER, UNIT, SOURCE, MOVES = "dispatcher", "ms", "program_span", "tokens_per_s"


def read(ctx):
    host_s = chunks = 0.0
    for r in ctx.records:
        spans = (((r.get("dispatch") or {}).get("stats") or {})
                 .get("spans") or {})
        if "dispatch.launch" in spans:
            host_s += sum(spans[k]["total_s"] for k in
                          ("dispatch.stage", "dispatch.launch") if k in spans)
            chunks += spans["dispatch.launch"]["n"]
    return 1e3 * host_s / chunks if chunks else None
