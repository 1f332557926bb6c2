"""KV cache: prompt tokens the prefix cache served over the prompt tokens of
the requests the engines admitted in the window, in percent."""


def read(run):
    hit = sum(run.stats_close[r]["prefix_cached_tokens"] - run.stats_open[r]["prefix_cached_tokens"]
              for r in run.stats_open)
    prompt = sum(len(s.job.prompt) for s in run.sent
                 if (req := run.server.get(s.rid)) is not None
                 and run.t_open <= req.t2 < run.t_close)
    return 100.0 * hit / prompt if prompt else None
