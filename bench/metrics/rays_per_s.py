"""Real rays of all tiles delivered inside the window, over the window.
Counted at dispatch (``rays_rendered``): at pipeline depth d up to d - 1
tiles are still in flight when the window closes."""


def read(run):
    rays = run.stats["rays_rendered"]
    return rays / run.window.seconds if rays else None
