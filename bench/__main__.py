import time

#: process start as near as Python allows: set-up is timed from here
T_PROCESS = time.perf_counter()

if __name__ == "__main__":
    from bench.run import main
    raise SystemExit(main(t_process=T_PROCESS))
