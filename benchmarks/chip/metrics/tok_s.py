"""tok_s: output tokens completed in the window over the window's wall
seconds (host clock)."""


def read(run):
    tokens = sum(len(o) for w in run.waves for o in w.outs)
    return tokens / run.window_s if run.window_s > 0 else None
