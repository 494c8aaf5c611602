"""Records of every word-count job finished in the window over the
window's length."""


def read(rec):
    done = [op["work"] for op in rec.ops if op["kind"] == "wordcount"
            and op.get("answer") is not None]
    return sum(done) / rec.window_s if done and rec.window_s > 0 else None
