"""A ratio of sums of the tick records' counters (`c` of
`obs/tickprof.py`, as the adapter hands them on in `ctx["counted"]`:
one dict per decode tick of the window, with the constants of the
configuration the adapter adds): sum(`counter`) / sum(`over`) x
`scale`, or with `complement` (1 - that ratio) x `scale`. None where
no tick counted `counter`: a program without it leaves the metric
out."""


def read(ctx, counter: str, over: str, scale: float = 1.0,
         complement: bool = False):
    ticks = [c for c in ctx.get("counted") or []
             if counter in c and over in c]
    den = sum(c[over] for c in ticks)
    if not den:
        return None
    ratio = sum(c[counter] for c in ticks) / den
    return float((1.0 - ratio if complement else ratio) * scale)
