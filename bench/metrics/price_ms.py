"""Pricing engine and table marshalling (``PricingEngine.price``): host
milliseconds per call, mean over the window's calls."""


def read(ctx):
    return ctx["spans"].mean_ms("price")
