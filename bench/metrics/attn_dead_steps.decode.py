"""Share of the paged-attention decode kernel's grid steps that stage no
live page, over the traced decode ticks, in %.

The kernel's grid is slots x pages-per-slot steps a layer, whatever the
rows hold: it walks retired rows and pages past each row's position too.
A decode row at position t has t // page + 1 live pages. The share is
1 - sum(live) / sum(grid) over the traced ticks that ran the decode
program; the layers cancel. The positions are the harness's, inferred from
the tokens it sees; the engine's own tick counters (`last_tick.live_pages`
and `grid_pages`) give the same sums."""


def read(run):
    if run.trace is None:
        return None
    ticks = [s for s in run.traced_steps() if s.decode]
    if not ticks:
        return None
    eng = run.engine
    ps = eng["page_size"]
    grid = eng["slots"] * (eng["max_tokens"] // ps) * len(ticks)
    live = sum(t // ps + 1 for s in ticks for t in s.decode)
    return 100.0 * (1.0 - live / grid)
