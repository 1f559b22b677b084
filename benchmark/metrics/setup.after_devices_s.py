"""Set-up from jax reaching the chip to the window's first send."""


def read(run):
    return (run.get('setup') or {}).get('after_devices_s')
