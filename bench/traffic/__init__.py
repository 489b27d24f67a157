"""Traffic drivers (``<kind>.py``, found by the ``kind`` of a traffic mix
file) and the device-side generators of tables and data they share."""
