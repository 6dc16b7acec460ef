"""lane_occupancy (scheduler): lane-steps that held a request over all
lane-steps of the window, in percent."""


def read(ctx):
    lanes = ctx.geo["lanes"]
    return 100.0 * sum(s.occupied for s in ctx.steps) / (
        len(ctx.steps) * lanes)
