"""Traffic drivers. A mix file names its driver (`"driver": "<name>"`),
and `run.py` imports `drivers/<name>.py` and builds its `Cell(conf,
model, mix)`, which has:

- `setup(seed)`: weights from the seed, every program the mix uses
  compiled, a warm pass; sets `setup_phases`, a dict of seconds;
- `window(seconds)`: the measured work; returns the window's length;
- `counts()`: `attempted` and `failed` for the result line;
- `release()`: frees the program's state before the check;
- `checks(limits)`: the numbers compared against the plain reference,
  each as `{"value": ..., "limit": ...}`.

Metric readers (`metrics/<name>.py`) read what the cell exposes.
"""
