"""Traffic kinds, one module each, found by the workload's ``traffic``.

Each module defines ``Traffic(dep, params, seed, spans)``, where ``dep`` is
the configuration's ``deploy.Deployment`` and ``params`` the workload
file's ``params``, with:

* ``setup()``: inputs from the seed, the fit, and every program the
  window can call, compiled (counted as set-up);
* ``window(seconds)``: the measured window;
* ``attempted`` / ``failed``: requests of the window, and those that never
  came back;
* ``end_to_end()``: {metric name: value} of what the window measured;
* ``layer``: what per-layer metric readers may read (records, counters);
* ``release()``: drop the program's state before the reference runs;
* ``readings(precision)``: the compared numbers, the program against the
  reference at ``precision``; ``control_readings()``: the reference one
  precision step lower put in the program's place.
"""
