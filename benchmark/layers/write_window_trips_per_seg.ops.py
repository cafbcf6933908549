from _window import trips_per_segment


def read(ctx):
    """Trips to a worker thread a windowed segment cost."""
    return trips_per_segment(ctx)
