"""Prompt tokens whose prefill ended in the window plus output tokens
emitted in it, over the window's length."""
import stats


def read(run):
    return stats.window_tokens(run.drive.records, run.seconds) / run.seconds
