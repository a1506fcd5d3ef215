"""Launcher — `python main.py --model-name seist_m_dpk --dataset-name diting ...`

Thin wrapper over seist_tpu.cli (the reference's root main.py equivalent).
"""

from seist_tpu.cli import main

if __name__ == "__main__":
    main()
