"""Prompt tokens the engine really prefilled per second of the window: the
prompt tokens of the requests it admitted in it, less those of them served from
cached blocks (``harness/drive_lm_server.py:admitted_between``)."""


def read(run, args):
    serve = run.get("serve")
    if not serve or not serve.get("prompt_tokens"):
        return None
    return (serve["prompt_tokens"] - serve["cached_tokens"]) / serve["window_s"]
