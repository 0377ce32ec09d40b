"""Percent of the configs a campaign's window built that the chip's
compiler refused: invalid trials that carry the compiler's error."""


def read(ctx):
    trials = ctx.window.trials
    if not trials:
        return None
    refused = sum(1 for t in trials if not t.valid
                  and not t.info.get("poison") and t.info.get("error"))
    return 100.0 * refused / len(trials)
