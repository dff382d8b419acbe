"""``setup.compile_s``: the harness's clock around the first call of each
compiled program (cache hit or miss), summed. Layer: entry and launcher."""


def read(trace, run, cell):
    return {"setup.compile_s": run["compile_s"]}
