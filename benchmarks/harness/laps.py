"""Seconds between marks, for the set-up breakdown a run prints."""
import time
from typing import Dict


class Laps:
    def __init__(self):
        self._t = time.perf_counter()
        self.seconds: Dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = round(now - self._t, 2)
        self._t = now
