"""Units of work (tokens, images) of all steps completed in the window
over the time from the window's start to the completion of the last."""


def read(ctx, unit):
    if ctx["traffic"]["unit"] != unit:
        return None
    w = ctx["window"]
    if not w["completed"]:
        return None
    return w["completed"] * ctx["units_per_step"] / w["elapsed_s"]
